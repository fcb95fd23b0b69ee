"""`model_type: nemotron_h` (NVIDIA Nemotron 3 Nano): every layer is ONE
sublayer behind ONE norm, and `hybrid_override_pattern` names each layer's:
`M` a Mamba-2 state-space mixer alone, `E` an expert layer alone, `*`
grouped-query attention alone. The published `modeling_nemotron_h.py` as the
checkpoint's config.json describes it, with no cache, no chunk form and no
kernels. D = hidden_size, n(x) = w * x / sqrt(mean(x^2) + eps) with a plain
weight and eps = layer_norm_epsilon:

  h0 = embeddings[ids]
  per layer: h = h + f(n(h))                 ONE norm, ONE sublayer
    M: zxbcdt = x W_in^T ; z | xBC | dt = d_in | d_in + 2 g s | heads columns,
         d_in = mamba_num_heads * mamba_head_dim (NOT expand * hidden_size)
       xBC = silu(causal depthwise conv1d(xBC, conv_kernel taps) + bias)
       xs | B | C = d_in | g s | g s ; heads // g heads share one B and C
       dt = softplus(dt + dt_bias) ; A = -exp(A_log)            one a head
       S_t = exp(dt_t A) S_{t-1} + dt_t outer(xs_t[head], B_t[group])
       y_t = S_t C_t[group] + D[head] xs_t          (a scan over t from 0)
       f = (rms_groups(y * silu(z); g groups) * norm.weight) W_out^T
           the gate BEFORE the norm; `time_step_limit` (0, inf): no clamp
    E: s = sigmoid(float32(x) float32(Wr)^T) over ALL the router's experts
       idx = top_k(s + e_score_correction_bias)  the bias moves the CHOICE
           only; n_group = topk_group = 1: no group limit
       w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
       f = shared(x) + sum over the row's experts THAT THIS SHARE HOLDS of
           w_j expert_j(x) ; expert(x) = (relu(x W_up^T) ** 2) W_down^T, TWO
           matrices, no gate, no bias; the shared expert the same form at
           width moe_shared_expert_intermediate_size
    *: q, k, v = x Wq^T, x Wk^T, x Wv^T (no bias) ; NO positional encoding
       (positions come from the M layers; `rope_theta` and
       `partial_rotary_factor` stand in the config unused) ; causal softmax
       at head_dim ** -0.5, heads // kv_heads query heads a K/V head ;
       f = attn Wo^T
  logits = n_f(h) head^T                      untied, no multiplier

Departures from the published description: none in the mathematics; the
depth, the pattern's cut, the experts held and the vocabulary are the
configuration's. What could not be confirmed here (no network) stands under
`cellbench.assumed` of the configuration's file: no rotary in the attention
layers, the tensor names, the router's product in float32.

`reference.py` hands `layer_forward` no layer index: a layer's KIND is read
from the leaves `layer_params` gave it (`in`: a mixer; `router`: an expert
layer; `q`: attention), so the choice is the tree's structure, static under
`jit`.

A share of a deployment: the checkpoint holds `n_routed_experts` experts,
`experts_held` = [first, count) of the published numbering, and the router
and its bias cover all `router_experts` of them (two keys of the
configuration's file beside the source's own). A pair whose expert lies on
another chip adds nothing here, in the program and in this reference alike.
Without the two keys every expert is held.
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _attention, _rms
from cellbench.roofline import BF16

F32 = 4
KINDS = {"M": "mamba", "E": "moe", "*": "full"}
# The plan's fills (`cellbench.assumed` of the configuration's file records
# the readings they were set from; scripts/nemotron_fill_readings.py reads
# them again). The default "bits" is |w| in 2**-9..2**-6, std 0.0137. Every
# sublayer reads rms-normed rows, so a layer's update has the size its
# weights give it whatever the residual holds: the fills below put each
# kind's update near the residual it is added to.
#   embeddings: rows of rms 0.5 (under "bits", 0.0137, the first expert
#     layer's update of 0.6 would BE the residual from then on);
#   in_proj "bits": z, xBC and dt's input have std 0.7 on normed rows;
#   the taps: xs, B, C near 0.3-0.5 after the convolution and silu, so that
#     S C and the skip D xs are of one order (under "bits" the taps leave
#     them at 0.01 and y * silu(z) under the grouped norm's eps);
#   A and dt_bias log-spaced: a head forgets in 1 / (dt A) tokens, dt near
#     1e-3..1e-1 and A 1..16: tens to thousands of tokens;
#   q and k: scores of std near 2, a query weighs some tens to hundreds of
#     8-12 k keys (under "bits", std 0.5, every query averages nearly all
#     keys and the layer's update is one direction for every row);
#   the router: logits of std 0.5 on the expert layers' normed rows (whose
#     gain is 0.577, below), the best scores near
#     0.75-0.85, where they still differ; its bias of the order of the gap
#     between the 6th and the 7th best score, so that it changes the choice
#     for a share of the pairs and is not the whole choice;
#   the expert layers' norm gain 0.577 (every other norm's is 1): relu(u) **
#     2 of u with std 0.7 has rms 0.6. With a gain of 1 three held pairs a
#     row at weights of 2.5 / 6 gave a routed sum of 0.27 and the shared
#     expert 0.52 of a residual near 2.7, and ONE pair's expert was 8% of a
#     row: a 6th and a 7th best score lie 0.006 apart (median), bfloat16's
#     error in a row (2-3% by layer 14) moves a score by 0.003, so a third
#     of the (row, expert layer) pairs chose another expert than float32
#     does, and sound runs read 0.05-0.16 by seed against the weakest
#     planted fault's 0.27 (my chip runs, PR 54, calls 1-3). A gain of
#     3 ** -0.5 makes u's std 0.41 and both sums a third: a routed sum of
#     0.09 beside a shared 0.17, one order, and a changed choice costs what
#     bfloat16's rounding costs anyway. (Call 4 had the same routed sum from
#     range fills of the 64 experts' down projections; 1.9 G values drawn
#     that way cost every run 45 s of set-up, a vector's gain nothing.) The
#     router's fill is 3 ** 0.5 times what it would be, so that its logits
#     keep the std of 0.5.
FILLS = {
    "moe_norm": {"low": 0.577, "high": 0.578},
    "embed": {"low": -0.85, "high": 0.85},
    "conv_w": {"low": -0.7, "high": 0.7},
    "a_log": {"low": 1, "high": 16, "spacing": "log", "then": "log"},
    "dt_bias": {"low": 1e-3, "high": 1e-1, "spacing": "log",
                "then": "softplus_inverse"},
    "d_skip": {"low": 0.05, "high": 0.2},
    "qk": {"low": -0.047, "high": 0.047},
    "router": {"low": -0.0277, "high": 0.0277},
    "expert_bias": {"low": -0.02, "high": 0.02},
}
# the int8 control quantises projections; the router stays as the checkpoint
# has it (a choice flipped by a rounded score is another expert, not a
# rounding), and so do its bias, the recurrence's vectors and the taps, as
# the program keeps them (models/wquant.py `QUANT_KEYS`)
INT8_KEEPS = ("router", "expert_bias", "a_log", "d", "dt_bias", "conv_w")


def _kind(config: dict, layer: int) -> str:
    return KINDS[config["hybrid_override_pattern"][layer]]


def _kinds(config: dict) -> list[str]:
    return [_kind(config, i) for i in range(config["num_hidden_layers"])]


def _dims(config: dict) -> dict:
    heads, hd = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    d_in = heads * hd
    conv_dim = d_in + 2 * groups * state
    return {"d_in": d_in, "heads": heads, "head_dim": hd, "groups": groups,
            "state": state, "conv": config["conv_kernel"],
            "conv_dim": conv_dim, "proj": d_in + conv_dim + heads}


def _router_width(config: dict) -> int:
    return config.get("router_experts", config["n_routed_experts"])


def _held(config: dict) -> tuple[int, int]:
    first, count = config.get(
        "experts_held", (0, config["n_routed_experts"]))
    return int(first), int(count)


def _shared_width(config: dict) -> int:
    return config["moe_shared_expert_intermediate_size"] * (
        config.get("n_shared_experts") or 0)


# ------------------------------------------------------- checkpoint plan
def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d = config["hidden_size"]
    p = f"backbone.layers.{layer}"
    m = f"{p}.mixer"
    kind = _kind(config, layer)
    tensors = [(f"{p}.norm.weight", (d,),
                FILLS["moe_norm"] if kind == "moe" else ONES)]
    if kind == "mamba":
        s = _dims(config)
        return tensors + [
            (f"{m}.in_proj.weight", (s["proj"], d)),
            (f"{m}.conv1d.weight", (s["conv_dim"], 1, s["conv"]),
             FILLS["conv_w"]),
            (f"{m}.conv1d.bias", (s["conv_dim"],)),
            (f"{m}.A_log", (s["heads"],), FILLS["a_log"]),
            (f"{m}.D", (s["heads"],), FILLS["d_skip"]),
            (f"{m}.dt_bias", (s["heads"],), FILLS["dt_bias"]),
            (f"{m}.norm.weight", (s["d_in"],), ONES),
            (f"{m}.out_proj.weight", (d, s["d_in"])),
        ]
    if kind == "moe":
        width, i = _router_width(config), config["moe_intermediate_size"]
        tensors += [
            (f"{m}.gate.weight", (width, d), FILLS["router"]),
            (f"{m}.gate.e_score_correction_bias", (width,),
             FILLS["expert_bias"]),
        ]
        first, count = _held(config)
        for e in range(first, first + count):
            tensors += [
                (f"{m}.experts.{e}.up_proj.weight", (i, d)),
                (f"{m}.experts.{e}.down_proj.weight", (d, i)),
            ]
        if _shared_width(config):
            tensors += [
                (f"{m}.shared_experts.up_proj.weight",
                 (_shared_width(config), d)),
                (f"{m}.shared_experts.down_proj.weight",
                 (d, _shared_width(config))),
            ]
        return tensors
    hd = config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return tensors + [
        (f"{m}.q_proj.weight", (q, d), FILLS["qk"]),
        (f"{m}.k_proj.weight", (kv, d), FILLS["qk"]),
        (f"{m}.v_proj.weight", (kv, d)),
        (f"{m}.o_proj.weight", (d, q)),
    ]


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    return [
        ("backbone.embeddings.weight", (v, d), FILLS["embed"]),
        ("backbone.norm_f.weight", (d,), ONES),
        ("lm_head.weight", (v, d)),
    ]


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]; the
    held experts stacked [E_held, out, in]. Still bfloat16 (exact). The
    leaves say the layer's kind: `in` a mixer, `router` an expert layer,
    `q` attention."""
    import numpy as np

    p = f"backbone.layers.{layer}."
    m = p + "mixer."
    out = {"ln": tensors[p + "norm.weight"]}
    kind = _kind(config, layer)
    if kind == "mamba":
        out.update({
            "in": tensors[m + "in_proj.weight"],
            "conv_w": tensors[m + "conv1d.weight"],
            "conv_b": tensors[m + "conv1d.bias"],
            "a_log": tensors[m + "A_log"],
            "d": tensors[m + "D"],
            "dt_bias": tensors[m + "dt_bias"],
            "ssm_norm": tensors[m + "norm.weight"],
            "out": tensors[m + "out_proj.weight"],
        })
    elif kind == "moe":
        out["router"] = tensors[m + "gate.weight"]
        out["expert_bias"] = tensors[m + "gate.e_score_correction_bias"]
        first, count = _held(config)
        for k in ("up", "down"):
            out[f"e_{k}"] = np.stack([
                tensors[m + f"experts.{e}.{k}_proj.weight"]
                for e in range(first, first + count)
            ])
            if _shared_width(config):
                out[f"s_{k}"] = tensors[m + f"shared_experts.{k}_proj.weight"]
    else:
        out.update({k: tensors[m + f"{k}_proj.weight"] for k in "qkvo"})
    return out


def mixer_forward(p: dict, config: dict, h, with_state: bool = False):
    """The Mamba-2 mixer for one sequence from an empty state: h [T, D] (the
    normed input) -> [T, D]. One recurrence step a token. `with_state`: also
    the final state [heads, head_dim, state] (scripts' readings)."""
    import jax
    import jax.numpy as jnp

    s = _dims(config)
    t = h.shape[0]
    heads, hd, groups, n = s["heads"], s["head_dim"], s["groups"], s["state"]
    zxbcdt = h @ p["in"].T
    z, xbc, dt = jnp.split(zxbcdt, [s["d_in"], s["d_in"] + s["conv_dim"]], -1)
    k = s["conv"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    w = p["conv_w"][:, 0, :]  # [C, K]
    xbc = sum(padded[i: i + t] * w[:, i] for i in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, b, c = jnp.split(xbc, [s["d_in"], s["d_in"] + groups * n], -1)
    xs = xs.reshape(t, heads, hd)
    rep = heads // groups
    b = jnp.repeat(b.reshape(t, groups, n), rep, axis=1)  # [T, H, N]
    c = jnp.repeat(c.reshape(t, groups, n), rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, H]
    a = -jnp.exp(p["a_log"])  # [H]

    def step(state, row):
        x_t, b_t, c_t, dt_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, (jnp.einsum("hpn,hn->hp", state, c_t)
                       + p["d"][:, None] * x_t)

    last, y = jax.lax.scan(
        step, jnp.zeros((heads, hd, n), jnp.float32), (xs, b, c, dt))
    y = y.reshape(t, s["d_in"]) * jax.nn.silu(z)  # the gate BEFORE the norm
    y = y.reshape(t, groups, -1)
    y = y * jax.lax.rsqrt(
        jnp.mean(jnp.square(y), -1, keepdims=True)
        + config["layer_norm_epsilon"])
    out = (y.reshape(t, s["d_in"]) * p["ssm_norm"]) @ p["out"].T
    return (out, last) if with_state else out


def relu2_mlp(x, up, down):
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(x @ up.T)) @ down.T


def route(logits, bias, config: dict):
    """The sigmoid router on logits [R, E] float32: (indices [R, k], weights
    [R, k]). The bias is added for the CHOICE; the weights are the chosen
    experts' unbiased scores, over their sum (norm_topk_prob), times
    routed_scaling_factor."""
    import jax

    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias, config["num_experts_per_tok"])
    top = jax.numpy.take_along_axis(scores, idx, axis=-1)
    if config.get("norm_topk_prob", True):
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    return idx, top * config.get("routed_scaling_factor", 1.0)


def moe(x, p: dict, config: dict, block: int = 256):
    """The expert layer's sublayer on normed rows [R, D], a block of rows at
    a time: the held experts' weighted sum plus the shared expert."""
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
        u = jnp.einsum("rd,eid->rei", rows, p["e_up"])
        hid = jnp.square(jax.nn.relu(u)) * w[:, :, None]
        return jnp.einsum("rei,edi->rd", hid, p["e_down"])

    out = jax.lax.map(one, xb).reshape(-1, d)[:r]
    if "s_up" in p:
        out = out + relu2_mlp(x, p["s_up"], p["s_down"])
    return out


def attention(p: dict, config: dict, x, positions):
    """Attention on one sequence's normed rows x [T, D], no positional
    encoding: [T, heads * head_dim], before o_proj."""
    t, hd = x.shape[0], config["head_dim"]
    q = (x @ p["q"].T).reshape(t, -1, hd)
    k = (x @ p["k"].T).reshape(t, -1, hd)
    v = (x @ p["v"].T).reshape(t, -1, hd)
    return _attention(q, k, v, positions, 0)


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = _rms(hidden, p["ln"], config["layer_norm_epsilon"])
    if "in" in p:
        return hidden + mixer_forward(p, config, x)
    if "router" in p:
        return hidden + moe(x, p, config)
    return hidden + attention(p, config, x, positions) @ p["o"].T


def embed(client: dict, config: dict, ids):
    import numpy as np

    return np.asarray(client["backbone.embeddings.weight"][ids], np.float32)


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    norm, head = (jnp.asarray(client[name]).astype(jnp.float32)
                  for name in ("backbone.norm_f.weight", "lm_head.weight"))
    return _rms(hidden_rows, norm, config["layer_norm_epsilon"]) @ head.T


# -------------------------------------------------------- roofline needs
def _attention_weights(config: dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return 2 * d * (q + kv)  # q, o; k, v


def _mixer_weights(config: dict) -> int:
    d, s = config["hidden_size"], _dims(config)
    return d * s["proj"] + s["d_in"] * d


def _kv_row_bytes(config: dict) -> int:
    """One token's K and V in one attention layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BF16


def _state_bytes(config: dict) -> int:
    """One sequence's recurrent state in one mixer layer: S in float32 and
    the convolution's tail in bfloat16."""
    s = _dims(config)
    return (s["heads"] * s["head_dim"] * s["state"] * F32
            + (s["conv"] - 1) * s["conv_dim"] * BF16)


def _expert_reach(config: dict, rows: float) -> tuple[float, float]:
    """(held pairs a row, distinct held experts `rows` rows reach), in
    expectation under routing that is uniform over experts."""
    _, count = _held(config)
    p = config["num_experts_per_tok"] / _router_width(config)
    return count * p, count * (1.0 - (1.0 - p) ** rows)


def experts_needs(config: dict, rows: float, kind: str) -> dict:
    """What the scopes `moe_shared` + `moe_experts` need over the
    configuration's expert layers for `rows` rows (`kind` "chunk" or
    "decode": the same count): the distinct held experts the rows reach,
    each one's TWO matrices at the PUBLISHED width `moe_intermediate_size`
    read once a layer (the zero columns a loader pads a stack with are no
    needed bytes), the shared expert's two matrices once, the rows in and
    out of both; the FLOPs of the rows' held pairs and of the shared expert
    on every row. No router."""
    d = config["hidden_size"]
    layers = _kinds(config).count("moe")
    expert = 2 * d * config["moe_intermediate_size"]
    shared = 2 * d * _shared_width(config)
    pairs, distinct = _expert_reach(config, rows)
    return {
        "bytes": layers * (
            (distinct * expert + shared) * BF16 + 4 * rows * d * BF16),
        "flops": layers * rows * 2 * (pairs * expert + shared),
    }


def _needs(config: dict, rows: float, context: float, kind: str) -> dict:
    """Each layer by its kind. A mixer: its two projections once, the
    state of every sequence the rows belong to read and written once
    ("decode": a row its own; "chunk": one sequence's), a recurrence step
    a row (6 FLOPs a state element: decay, update, read-out). An expert
    layer: the router, `experts_needs`. An attention layer: its four
    projections, K and V of the context read once ("decode": a row its own;
    "chunk": one sequence's once for all rows) and the rows' own written
    once. The rows' activations in and out of every layer."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, s = config["num_attention_heads"], _dims(config)
    kinds = _kinds(config)
    n_m, n_e, n_a = (kinds.count(k) for k in ("mamba", "moe", "full"))
    seqs = rows if kind == "decode" else 1
    router = d * _router_width(config)
    experts = experts_needs(config, rows, kind)
    weights = (n_m * _mixer_weights(config) + n_a * _attention_weights(config)
               + n_e * router) * BF16
    expert_bytes = experts["bytes"] - n_e * 4 * rows * d * BF16
    state = n_m * seqs * 2 * _state_bytes(config)
    if kind == "decode":
        kv = n_a * rows * (context + 2) * _kv_row_bytes(config)
        attended = context + 1
    else:
        kv = n_a * (context + 2 * rows) * _kv_row_bytes(config)
        attended = context + rows / 2
    flops = rows * (
        n_m * (2 * _mixer_weights(config)
               + 6 * s["heads"] * s["head_dim"] * s["state"]
               + 2 * s["conv"] * s["conv_dim"])
        + n_a * (2 * _attention_weights(config) + 4 * attended * heads * hd)
        + n_e * 2 * router) + experts["flops"]
    weight_bytes = weights + expert_bytes
    return {"bytes": weight_bytes + kv + state
            + len(kinds) * 2 * rows * d * BF16,
            "flops": flops, "weight_bytes": weight_bytes, "kv_bytes": kv,
            "state_bytes": state}


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """One decode step of `rows` rows at mean context `context`."""
    return _needs(config, rows, context, "decode")


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """One prefill chunk of `rows` tokens of ONE sequence with `context`
    tokens cached."""
    return _needs(config, rows, context, "chunk")


def ssm_scan_needs(config: dict, rows: float, kind: str) -> dict:
    """What the `ssm_scan` scope alone needs over the configuration's MIXER
    layers (the `M` of the pattern: an expert or attention layer has no
    scan): the convolution, dt, and the recurrence (`kind` "decode": `rows`
    rows, each its own state read and written once; "chunk": `rows` tokens
    of one sequence, its state once each way). No projection's weights."""
    layers, s = _kinds(config).count("mamba"), _dims(config)
    seqs = rows if kind == "decode" else 1
    io = (rows * (2 * s["conv_dim"] + s["heads"]) * BF16
          + rows * s["d_in"] * F32)
    small = (s["conv"] + 1) * s["conv_dim"] * BF16 + 3 * s["heads"] * F32
    return {
        "bytes": layers * (seqs * 2 * _state_bytes(config) + io + small),
        "flops": layers * rows * (
            6 * s["heads"] * s["head_dim"] * s["state"]
            + 2 * s["conv"] * s["conv_dim"]),
    }
