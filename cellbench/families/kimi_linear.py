"""`model_type: kimi_linear` (Moonshot Kimi Linear, arXiv:2510.26692): Kimi
delta attention (KDA) layers, a delta rule whose decay is a VECTOR a key
channel, with a latent-attention (MLA) layer that has no positional encoding
after every three; layer 0's MLP dense, every other layer's a sparse one
behind a sigmoid router whose choice a bias corrects, beside one ungated
shared expert. Written from the published `config.json` and the description
of `modeling_kimi.py`, token by token and with no cache (the program's chunk
form, its sub-blocks and its two arenas are not used here). D = hidden_size,
n(x; w) = w * x / sqrt(mean(x^2) + eps) with a plain weight:

  x += mixer(n(x)) ; x += mlp(n(x))
  which mixer: `linear_attn_config.full_attn_layers` / `kda_layers`, 1-indexed
  KDA, on h = n(x):
    q, k, v = silu(conv(h Wq^T)), silu(conv(h Wk^T)), silu(conv(h Wv^T))
        three causal depthwise convolutions of `short_conv_kernel_size`, no bias
    per head: q = l2norm(q) * d_k ** -0.5 ; k = l2norm(k)   (eps 1e-6 inside
        the root)
    g = -exp(A_log[head]) * softplus((h W_fa^T) W_fb^T + dt_bias)  [T, H, d_k]
    beta = sigmoid(h W_b^T)                                        [T, H]
    per head, S [d_k, d_v] from zeros, a scan over t:
        S = diag(exp(g_t)) S ; u = beta_t (v_t - S^T k_t) ; S += k_t u^T ;
        o_t = S^T q_t
    out = (n_head(o; w_o_norm) * sigmoid((h W_ga^T) W_gb^T)) W_o^T
  MLA, on h = n(x): q = h Wq^T -> per head q_nope | q_pe (`q_lora_rank` null:
    one projection, no query norm) ; [c_kv | k_pe] = h Wkva^T ; c_kv = n(c_kv)
    ; k_pe ONE key for all heads ; `mla_use_nope`: NO rotary on q_pe or k_pe ;
    [k_nope | v] = c_kv Wkvb^T per head ;
    score = (q_nope . k_nope + q_pe . k_pe) * (nope + rope) ** -0.5 ; causal
    softmax ; out = concat_h(p v) Wo^T                      (the EXPANDED form)
  MLP: layer < first_k_dense_replace: silu_mlp of intermediate_size; else
    s = sigmoid(float32(g Wr^T)) over ALL the router's experts ;
    idx = top_k(s + e_score_correction_bias)  (one group: the plain top-k) ;
    w = s[idx] / (sum s[idx] + 1e-20) (moe_renormalize) * routed_scaling_factor
    m = shared(g) + sum over the row's experts THAT THIS SHARE HOLDS of
        w_j * expert_j(g)
  logits = n(x) head^T                                      untied

Departures from the published description: none in the mathematics; the
depth, the experts held and the vocabulary are the configuration's cut. What
the config does not give (the gates' rank, l2norm's eps, `A_log`'s stored
shape [1, 1, H, 1], every tensor's name) is from memory of the published
modeling file and checkpoint and listed in the configuration's `assumed`.

`reference.py` hands `layer_forward` no layer index: a layer's KIND is read
from the leaves `layer_params` gave it (`conv_q`: a KDA layer, else latent
attention; `router`: a sparse MLP, else dense).

A share of a deployment: the checkpoint holds `num_experts` experts,
`experts_held` = [first, count) of the published numbering, and the router
and its bias cover all `router_experts` of them (two keys of the
configuration's file beside the source's own). A pair whose expert lies on
another chip adds nothing here, in the program and in this reference alike.
Without the two keys every expert is held.
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _rms
from cellbench.roofline import BF16

F32 = 4
# The plan's fills (`cellbench.assumed` of the configuration's file records
# the readings they were set from). The default "bits" is |w| in 2**-9..2**-6.
#   o_proj (KDA's), down projections: narrower, so a layer's update stays
#     near the residual it is added to and rows keep their own direction
#     (PR 35's reading, PR 39's the same);
#   the latent layers' o_proj: wider than that, narrower than "bits" (PR 39:
#     softmax attention's update is mostly one vector for every row);
#   the latent layers' q_proj: wider, so a head's scores have a standard
#     deviation near 2 and attention depends on the context: at "bits" it is
#     0.3 and every query averages all its keys alike (PR 47's q/k finding);
#   the router: logits of std 0.5 on normed rows (PR 47), the bias of the
#     order of the gap between the 8th and the 9th best score;
#   conv taps: as Qwen3-Next's, so the convolved v stays of order 1;
#   A_log, dt_bias, f_b: the published initialisation (A ~ U(1, 16),
#     dt from 1e-3..1e-1 over heads AND channels) forgets within a few
#     tokens at its upper end. Here A in 0.1..1 (log-spaced over heads),
#     softplus(dt_bias) in 2e-3..1e-1 (log-spaced over a head's CHANNELS),
#     so a channel's memory 1 / |g| spans ten to five thousand tokens and
#     the decay differs by more than a decade inside every head (at a
#     constant decay a head the model IS Gated DeltaNet and the
#     `scalar_decay` fault could not be caught); f_b wider than "bits", so
#     the decay follows the token (exp(+-0.4)) and is no constant.
FILLS = {
    "o_proj": {"low": -0.006, "high": 0.006},
    "attn_o_proj": {"low": -0.015, "high": 0.015},
    "attn_q_proj": {"low": -0.15, "high": 0.15},
    "down_proj": {"low": -0.006, "high": 0.006},
    "router": {"low": -0.018, "high": 0.018},
    "expert_bias": {"low": -0.02, "high": 0.02},
    "conv": {"low": -0.7, "high": 0.7},
    "A_log": {"low": 0.1, "high": 1.0, "spacing": "log", "then": "log"},
    "dt_bias": {"low": 2e-3, "high": 1e-1, "spacing": "log",
                "then": "softplus_inverse"},
    "f_b": {"low": -0.1, "high": 0.1},
}
# the int8 control quantises projections; what stays as the checkpoint has
# it there stays so in the program's `--weight-quant int8` (models/wquant.py
# QUANT_KEYS): the router (a choice flipped by a rounded score is another
# expert, not a rounding), the taps (4 numbers a channel), A_log (stored
# [1, 1, H, 1]) and the narrow low-rank pairs of the decay, the gate and beta
INT8_KEEPS = ("router", "conv_q", "conv_k", "conv_v", "a_log", "f_a", "f_b",
              "g_a", "g_b", "b")


def _lin(config: dict) -> dict:
    lin = config["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]
    return {"h": h, "dk": dk, "dv": dk, "d_key": h * dk, "rank": dk,
            "conv": lin["short_conv_kernel_size"]}


def is_full(config: dict, layer: int) -> bool:
    return layer + 1 in config["linear_attn_config"]["full_attn_layers"]


def _sparse(config: dict, layer: int) -> bool:
    return bool(config.get("num_experts")) and layer >= config.get(
        "first_k_dense_replace", 0)


def _router_width(config: dict) -> int:
    return config.get("router_experts", config["num_experts"])


def _held(config: dict) -> tuple[int, int]:
    first, count = config.get("experts_held", (0, config["num_experts"]))
    return int(first), int(count)


def _shared_width(config: dict) -> int:
    return config["moe_intermediate_size"] * (
        config.get("num_shared_experts") or 0)


# ------------------------------------------------------- checkpoint plan
def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, m = config["hidden_size"], _lin(config)
    p = f"model.layers.{layer}"
    a = f"{p}.self_attn"
    tensors = [
        (f"{p}.input_layernorm.weight", (d,), ONES),
        (f"{p}.post_attention_layernorm.weight", (d,), ONES),
    ]
    if is_full(config, layer):
        heads = config["num_attention_heads"]
        nope, rope, vd = (config["qk_nope_head_dim"],
                          config["qk_rope_head_dim"], config["v_head_dim"])
        kvr = config["kv_lora_rank"]
        tensors += [
            (f"{a}.q_proj.weight", (heads * (nope + rope), d),
             FILLS["attn_q_proj"]),
            (f"{a}.kv_a_proj_with_mqa.weight", (kvr + rope, d)),
            (f"{a}.kv_a_layernorm.weight", (kvr,), ONES),
            (f"{a}.kv_b_proj.weight", (heads * (nope + vd), kvr)),
            (f"{a}.o_proj.weight", (d, heads * vd), FILLS["attn_o_proj"]),
        ]
    else:
        dkey = m["d_key"]
        tensors += [(f"{a}.{x}_proj.weight", (dkey, d)) for x in "qkv"]
        tensors += [
            (f"{a}.{x}_conv1d.weight", (dkey, 1, m["conv"]), FILLS["conv"])
            for x in "qkv"]
        tensors += [
            (f"{a}.A_log", (1, 1, m["h"], 1), FILLS["A_log"]),
            (f"{a}.dt_bias", (dkey,), FILLS["dt_bias"]),
            (f"{a}.f_a_proj.weight", (m["rank"], d)),
            (f"{a}.f_b_proj.weight", (dkey, m["rank"]), FILLS["f_b"]),
            (f"{a}.b_proj.weight", (m["h"], d)),
            (f"{a}.g_a_proj.weight", (m["rank"], d)),
            (f"{a}.g_b_proj.weight", (dkey, m["rank"])),
            (f"{a}.o_norm.weight", (m["dv"],), ONES),
            (f"{a}.o_proj.weight", (d, dkey), FILLS["o_proj"]),
        ]
    if not _sparse(config, layer):
        i = config["intermediate_size"]
        return tensors + [
            (f"{p}.mlp.gate_proj.weight", (i, d)),
            (f"{p}.mlp.up_proj.weight", (i, d)),
            (f"{p}.mlp.down_proj.weight", (d, i), FILLS["down_proj"]),
        ]
    s = f"{p}.block_sparse_moe"
    width, i = _router_width(config), config["moe_intermediate_size"]
    tensors += [
        (f"{s}.gate.weight", (width, d), FILLS["router"]),
        (f"{s}.gate.e_score_correction_bias", (width,), FILLS["expert_bias"]),
    ]
    first, count = _held(config)
    for e in range(first, first + count):
        # w1 the gate, w3 the up, w2 the down projection
        tensors += [
            (f"{s}.experts.{e}.w1.weight", (i, d)),
            (f"{s}.experts.{e}.w3.weight", (i, d)),
            (f"{s}.experts.{e}.w2.weight", (d, i), FILLS["down_proj"]),
        ]
    if _shared_width(config):
        w = _shared_width(config)
        tensors += [
            (f"{s}.shared_experts.gate_proj.weight", (w, d)),
            (f"{s}.shared_experts.up_proj.weight", (w, d)),
            (f"{s}.shared_experts.down_proj.weight", (d, w),
             FILLS["down_proj"]),
        ]
    return tensors


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    tensors = [
        ("model.embed_tokens.weight", (v, d)),
        ("model.norm.weight", (d,), ONES),
    ]
    if not config.get("tie_word_embeddings", False):
        tensors.append(("lm_head.weight", (v, d)))
    return tensors


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]; the
    held experts stacked [E_held, out, in]. Still bfloat16 (exact). A KDA
    layer carries `conv_q`, a sparse layer `router`."""
    import numpy as np

    p = f"model.layers.{layer}."
    a = p + "self_attn."
    out = {
        "ln1": tensors[p + "input_layernorm.weight"],
        "ln2": tensors[p + "post_attention_layernorm.weight"],
        "o": tensors[a + "o_proj.weight"],
    }
    if is_full(config, layer):
        out.update({
            "q": tensors[a + "q_proj.weight"],
            "kv_a": tensors[a + "kv_a_proj_with_mqa.weight"],
            "kv_a_norm": tensors[a + "kv_a_layernorm.weight"],
            "kv_b": tensors[a + "kv_b_proj.weight"],
        })
    else:
        for x in "qkv":
            out[x] = tensors[a + f"{x}_proj.weight"]
            out[f"conv_{x}"] = tensors[a + f"{x}_conv1d.weight"]
        out.update({
            "a_log": tensors[a + "A_log"],
            "dt_bias": tensors[a + "dt_bias"],
            "o_norm": tensors[a + "o_norm.weight"],
            **{k: tensors[a + f"{k}_proj.weight"]
               for k in ("f_a", "f_b", "g_a", "g_b", "b")},
        })
    if not _sparse(config, layer):
        for k in ("gate", "up", "down"):
            out[k] = tensors[p + f"mlp.{k}_proj.weight"]
        return out
    s = p + "block_sparse_moe."
    out["router"] = tensors[s + "gate.weight"]
    out["expert_bias"] = tensors[s + "gate.e_score_correction_bias"]
    first, count = _held(config)
    for k, w in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
        out[f"e_{k}"] = np.stack([
            tensors[s + f"experts.{e}.{w}.weight"]
            for e in range(first, first + count)
        ])
        if _shared_width(config):
            out[f"s_{k}"] = tensors[s + f"shared_experts.{k}_proj.weight"]
    return out


def kda_inputs(p: dict, config: dict, h):
    """The rule's inputs for one sequence from an empty convolution tail:
    (q, k [T, H, dk], v [T, H, dv], g [T, H, dk], beta [T, H])."""
    import jax
    import jax.numpy as jnp

    m = _lin(config)
    t, heads, dk = h.shape[0], m["h"], m["dk"]

    def conv(x, w):  # w torch [C, 1, K]: causal, depthwise, no bias
        padded = jnp.pad(x, ((m["conv"] - 1, 0), (0, 0)))
        return jax.nn.silu(sum(
            padded[i: i + t] * w[:, 0, i] for i in range(m["conv"])))

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k, v = (conv(h @ p[x].T, p[f"conv_{x}"]).reshape(t, heads, dk)
               for x in "qkv")
    f = (h @ p["f_a"].T) @ p["f_b"].T + p["dt_bias"]
    g = -jnp.exp(p["a_log"].reshape(heads, 1)) * jax.nn.softplus(
        f.reshape(t, heads, dk))
    return (l2(q) * dk ** -0.5, l2(k), v, g, jax.nn.sigmoid(h @ p["b"].T))


def kimi_delta_attention(p: dict, config: dict, h):
    """The KDA mixer for one sequence from an empty state: h [T, D] (the
    normed input) -> [T, D]; the recurrence token by token."""
    import jax
    import jax.numpy as jnp

    m = _lin(config)
    q, k, v, g, beta = kda_inputs(p, config, h)

    def step(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = s * jnp.exp(g_t)[:, :, None]  # a decay a ROW of S
        u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * beta_t[:, None]
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((m["h"], m["dk"], m["dv"]), jnp.float32),
        (q, k, v, g, beta))
    y = _rms(o, p["o_norm"], config["rms_norm_eps"])
    gate = jax.nn.sigmoid((h @ p["g_a"].T) @ p["g_b"].T)
    return (y.reshape(h.shape[0], -1) * gate) @ p["o"].T


def mla_attention(p: dict, config: dict, h, positions, block: int = 256):
    """Latent attention without positions on one sequence's normed rows h
    [T, D], expanded, a block of queries at a time: [T, D]."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads = config["num_attention_heads"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    kvr = config["kv_lora_rank"]
    q = (h @ p["q"].T).reshape(t, heads, nope + rope)
    ckv = h @ p["kv_a"].T
    c_kv = _rms(ckv[:, :kvr], p["kv_a_norm"], config["rms_norm_eps"])
    k_pe = ckv[:, kvr:]  # [T, rope]: no rotary (mla_use_nope)
    kv = (c_kv @ p["kv_b"].T).reshape(t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, heads, nope + rope)
    pb = jnp.pad(positions, (0, pad), mode="edge").reshape(-1, block)

    def one(args):
        qq, pp = args
        scores = (jnp.einsum("thn,shn->hts", qq[..., :nope], k_nope)
                  + jnp.einsum("thr,sr->hts", qq[..., nope:], k_pe)) * scale
        mask = pp[:, None] >= positions[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, (qb, pb)).reshape(-1, heads * vd)[:t]
    return out @ p["o"].T


def _silu_mlp(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def route(logits, bias, config: dict):
    """The sigmoid router on logits [R, E] float32: (indices [R, k], weights
    [R, k]). The bias is added for the CHOICE; the weights are the chosen
    experts' unbiased scores, over their sum (moe_renormalize), times
    routed_scaling_factor. One group: the grouped top-k is the plain one."""
    import jax

    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias, config["num_experts_per_token"])
    top = jax.numpy.take_along_axis(scores, idx, axis=-1)
    if config.get("moe_renormalize", True):
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return idx, top * config.get("routed_scaling_factor", 1.0)


def moe(x, p: dict, config: dict, block: int = 256):
    """The sparse MLP on normed rows [R, D], a block of rows at a time: the
    held experts' weighted sum plus the shared expert."""
    import jax
    import jax.numpy as jnp

    r, d = x.shape
    first, count = _held(config)
    pad = -r % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, d)

    def one(rows):
        idx, top = route(rows @ p["router"].T, p["expert_bias"], config)
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
    if "s_gate" in p:
        out = out + _silu_mlp(x, p["s_gate"], p["s_up"], p["s_down"])
    return out


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]. The layer's kind
    is what its tensors say."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = config["rms_norm_eps"]
    h = _rms(hidden, p["ln1"], eps)
    if "conv_q" in p:
        hidden = hidden + kimi_delta_attention(p, config, h)
    else:
        hidden = hidden + mla_attention(p, config, h, positions)
    x = _rms(hidden, p["ln2"], eps)
    if "router" in p:
        return hidden + moe(x, p, config)
    return hidden + _silu_mlp(x, p["gate"], p["up"], p["down"])


def embed(client: dict, config: dict, ids):
    import numpy as np

    return np.asarray(client["model.embed_tokens.weight"][ids], np.float32)


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    head = client.get("lm_head.weight", client["model.embed_tokens.weight"])
    norm, head = (jnp.asarray(w).astype(jnp.float32)
                  for w in (client["model.norm.weight"], head))
    return _rms(hidden_rows, norm, config["rms_norm_eps"]) @ head.T


# -------------------------------------------------------- roofline needs
def _kinds(config: dict) -> tuple[int, int]:
    """(KDA layers, latent layers) of the configuration's depth."""
    layers = config["num_hidden_layers"]
    full = sum(is_full(config, i) for i in range(layers))
    return layers - full, full


def _mixer_weights(config: dict) -> tuple[int, int]:
    """(a KDA layer's mixer, a latent layer's attention), in parameters."""
    d, m = config["hidden_size"], _lin(config)
    heads = config["num_attention_heads"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    kvr = config["kv_lora_rank"]
    kda = (4 * d * m["d_key"] + 2 * m["rank"] * (d + m["d_key"])
           + d * m["h"])
    mla = (d * heads * (nope + rope) + d * (kvr + rope)
           + kvr * heads * (nope + vd) + heads * vd * d)
    return kda, mla


def state_bytes(config: dict) -> int:
    """One sequence's recurrent state in one KDA layer: S in float32 and the
    convolution's tail (q | k | v channels) in bfloat16."""
    m = _lin(config)
    return (m["h"] * m["dk"] * m["dv"] * F32
            + (m["conv"] - 1) * 3 * m["d_key"] * BF16)


def latent_row_bytes(config: dict) -> int:
    """One token's cached row in one latent layer: the latent and the
    shared key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * BF16


def _absorbed_flops_per_key(config: dict) -> int:
    """Per query row and attended key, all heads, absorbed: scores over
    latent + shared-key dims, values over the latent."""
    kvr, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return 2 * config["num_attention_heads"] * ((kvr + rope) + kvr)


def mla_attention_needs(config: dict, rows: float, context: float,
                        kind: str = "chunk") -> dict:
    """What the attention core alone (`mla_attention` + `latent_io`) needs
    over the configuration's LATENT layers, in the ABSORBED form both paths
    run: "chunk": `rows` queries of ONE sequence with `context` tokens
    cached (the latent rows read once, the chunk's own written once, the
    causal lower triangle); "decode": `rows` sequences, each its own
    `context` latent rows once."""
    _, layers = _kinds(config)
    row = latent_row_bytes(config)
    heads, kvr, rope = (config["num_attention_heads"], config["kv_lora_rank"],
                        config["qk_rope_head_dim"])
    q_io = rows * heads * (2 * kvr + rope) * BF16  # q_lat, q_pe in; o_lat out
    if kind == "decode":
        attended, kv = context + 1, rows * (context + 1) * row
    else:
        attended, kv = context + rows / 2, (context + 2 * rows) * row
    return {"bytes": layers * (kv + q_io),
            "flops": layers * rows * attended * _absorbed_flops_per_key(config)}


# per row and state element: decay, S^T k, the rank-one update, S^T q
RULE_FLOPS = 7


def kda_rule_needs(config: dict, rows: float, kind: str) -> dict:
    """What the scopes `kda_conv` + `kda_rule` + `state_io` alone need over
    the configuration's KDA layers: the convolution, the gates' softplus and
    sigmoid, the recurrence (`kind` "decode": `rows` rows, each its own
    state read and written once; "chunk": `rows` tokens of one sequence, its
    state once each way). No projection's weights. A row's decay is a
    VECTOR: its bytes are the key's."""
    n_lin, _ = _kinds(config)
    m = _lin(config)
    seqs = rows if kind == "decode" else 1
    # in: q | k | v before the taps (bf16), the decay's input (f32 a key
    # channel), beta (f32 a head); out: o (f32 a value channel)
    io = rows * (3 * m["d_key"] * BF16 + m["d_key"] * F32 + m["h"] * F32
                 + m["d_key"] * F32)
    small = m["conv"] * 3 * m["d_key"] * BF16 + (m["d_key"] + m["h"]) * F32
    return {
        "bytes": n_lin * (seqs * 2 * state_bytes(config) + io + small),
        "flops": n_lin * rows * (
            RULE_FLOPS * m["h"] * m["dk"] * m["dv"]
            + 2 * m["conv"] * 3 * m["d_key"]),
    }


def _expert_reach(config: dict, rows: float) -> tuple[float, float]:
    """(held pairs a row, distinct held experts `rows` rows reach), in
    expectation under routing that is uniform over experts."""
    _first, count = _held(config)
    p = config["num_experts_per_token"] / _router_width(config)
    return count * p, count * (1.0 - (1.0 - p) ** rows)


def _needs(config: dict, rows: float, attention: dict, state: float) -> dict:
    """Every layer's mixer weights once; a dense layer's MLP; a sparse
    layer's router, shared expert and the distinct held experts the rows
    reach, each row computing its held pairs; the latent layers' attention
    core as `attention` states; `state` bytes of recurrent state and one
    rule step a row in the KDA layers; the rows' activations."""
    layers, d = config["num_hidden_layers"], config["hidden_size"]
    m = _lin(config)
    n_lin, n_full = _kinds(config)
    kda_w, mla_w = _mixer_weights(config)
    dense = sum(not _sparse(config, i) for i in range(layers))
    sparse = layers - dense
    pairs, distinct = _expert_reach(config, rows)
    expert = 3 * d * config["moe_intermediate_size"]
    shared = 3 * d * _shared_width(config)
    router = d * _router_width(config)
    mlp = 3 * d * config["intermediate_size"]
    weights = (n_lin * kda_w + n_full * mla_w + dense * mlp
               + sparse * (router + shared + distinct * expert))
    flops = rows * (
        2 * (n_lin * kda_w + n_full * mla_w + dense * mlp
             + sparse * (router + shared + pairs * expert))
        + n_lin * RULE_FLOPS * m["h"] * m["dk"] * m["dv"]
    ) + attention["flops"]
    return {"bytes": (weights * BF16 + attention["bytes"] + state
                      + 2 * rows * d * BF16),
            "flops": flops, "weight_bytes": weights * BF16,
            "kv_bytes": attention["bytes"], "state_bytes": state}


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """One decode step of `rows` rows at mean context `context`: absorbed
    attention streaming every row's latent pages once in the latent layers,
    every row's state read and written once in the KDA layers."""
    n_lin, _ = _kinds(config)
    return _needs(config, rows,
                  mla_attention_needs(config, rows, context, "decode"),
                  n_lin * rows * 2 * state_bytes(config))


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """One prefill chunk of `rows` tokens of ONE sequence with `context`
    tokens cached: absorbed attention over the cached latent rows and the
    chunk's own in the latent layers; the sequence's state once each way in
    the KDA layers; the held stacks read once."""
    n_lin, _ = _kinds(config)
    return _needs(config, rows,
                  mla_attention_needs(config, rows, context, "chunk"),
                  n_lin * 2 * state_bytes(config))
