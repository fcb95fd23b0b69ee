"""Plain reference for every configuration, independent of the program.

The published forward pass of a decoder stack in straightforward jax.numpy
and float32 (`highest` matmul precision, set by the caller): no kernels, no
cache, no batching tricks. Its inputs are the checkpoint files the benchmark
wrote from --seed (parsed here, not through the program's loader) and token
ids from the same seed. Departures from the published description: none; the
depth is the configuration's cut.

  hidden = family.embed(client tensors, ids)
  per layer: hidden = family.layer_forward(family.layer_params(file), hidden)
  logits = family.logits_rows(client tensors, hidden at the judged rows)

The layer and the two ends are the family's (cellbench/families/<model_type>
.py, which also says what they compute); what every family shares is here:
the safetensors reader, the layer loop with read-ahead, the int8 control,
`compare`, and plain helpers for a family file to import (`_rms`,
`_rotate_half`, `_attention`, `_rope_attention`). No tensor's name is known
here.
"""

from __future__ import annotations

import json
import pathlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from cellbench import families
from cellbench.checkpoint import CLIENT_SHARD, file_name, layer_tag


def read_safetensors(path: pathlib.Path) -> dict[str, np.ndarray]:
    """name -> array; BF16 tensors come back as ml_dtypes.bfloat16 views."""
    import ml_dtypes

    raw = np.fromfile(path, dtype=np.uint8)
    n = int.from_bytes(raw[:8].tobytes(), "little")
    header = json.loads(raw[8: 8 + n].tobytes())
    body = raw[8 + n:]
    out = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        if meta["dtype"] != "BF16":
            raise ValueError(f"{path}: {name} is {meta['dtype']}, not BF16")
        a, b = meta["data_offsets"]
        out[name] = body[a:b].view(ml_dtypes.bfloat16).reshape(meta["shape"])
    return out


def layer_params(ckpt: pathlib.Path, config: dict, layer: int) -> dict:
    """One layer's file, as its family's `layer_params` names its leaves."""
    tensors = read_safetensors(ckpt / file_name(layer_tag(layer)))
    return families.of(config).layer_params(tensors, config, layer)


def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jnp.reciprocal(jnp.sqrt(var + eps)) * w


def _rotate_half(x):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _attention(q, k, v, positions, window, block: int = 512):
    """Causal (and windowed) softmax attention for one sequence, a block of
    queries at a time: q [T, H, hd], k and v [T, Hkv, hd]."""
    import jax
    import jax.numpy as jnp

    t, heads, hd = q.shape
    kvh = k.shape[1]
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    qb = qb.reshape(-1, block, kvh, heads // kvh, hd)
    pb = jnp.pad(positions, (0, pad), mode="edge").reshape(-1, block)

    def one(args):
        qq, pp = args
        scores = jnp.einsum("tgrh,sgh->grts", qq, k) / np.sqrt(hd)
        dist = pp[:, None] - positions[None, :]
        mask = dist >= 0
        if window:
            mask &= dist < window
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("grts,sgh->tgrh", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(one, (qb, pb)).reshape(-1, heads * hd)[:t]


def _rope_attention(q, k, v, positions, theta: float, window: int):
    """Rotary positions (HF rotate_half) on q [T, H, hd] and k [T, Hkv, hd],
    then `_attention`: [T, H * hd]."""
    import jax.numpy as jnp

    hd = q.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    q = q * jnp.cos(ang) + _rotate_half(q) * jnp.sin(ang)
    k = k * jnp.cos(ang) + _rotate_half(k) * jnp.sin(ang)
    return _attention(q, k, v, positions, window)


def int8_weights(p: dict, keep: tuple[str, ...] = ()) -> dict:
    """The control's weights: every array of two or more dimensions as
    symmetric int8 codes with one scale per output channel, dequantised
    (vectors, and the leaves a family names in `keep`, untouched): the
    nearest precision below the bfloat16 the configurations state."""
    import jax.numpy as jnp

    def quant(w):
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale

    return {name: w if w.ndim < 2 or name in keep else quant(w)
            for name, w in p.items()}


def reference_logits(ckpt: pathlib.Path, config: dict, ids: np.ndarray,
                     rows: list[tuple[int, int]],
                     timing: dict | None = None) -> dict:
    """Float32 logits at (sequence, position) pairs `rows` for right-padded
    token ids [B, T] (causal attention keeps padding out of real positions):
    {"exact": the reference, "int8": the same forward pass with int8_weights,
    the control the comparison must tell apart from the reference}."""
    import jax
    import jax.numpy as jnp

    family = families.of(config)
    keep = getattr(family, "INT8_KEEPS", ())
    client = read_safetensors(ckpt / file_name(CLIENT_SHARD))
    positions = jnp.arange(ids.shape[1])
    hidden = jnp.asarray(family.embed(client, config, ids))

    def forward(p, hs):
        return jax.lax.map(
            lambda h: family.layer_forward(p, config, h, positions), hs)

    step = jax.jit(lambda p, hs, hq: (
        forward(p, hs), forward(int8_weights(p, keep), hq)))
    exact = low = hidden
    timing = {} if timing is None else timing
    timing["forward_by_layer"] = []
    clock = time.perf_counter
    layers = range(config["num_hidden_layers"])
    with ThreadPoolExecutor(max_workers=3) as readers:  # read ahead
        ahead = {i: readers.submit(layer_params, ckpt, config, i)
                 for i in layers[:3]}
        for layer in layers:
            t0 = clock()
            host = ahead.pop(layer).result()
            if layer + 3 in layers:
                ahead[layer + 3] = readers.submit(
                    layer_params, ckpt, config, layer + 3)
            t1 = clock()
            p = jax.block_until_ready(jax.tree.map(jnp.asarray, host))
            t2 = clock()
            exact, low = jax.block_until_ready(step(p, exact, low))
            timing["forward_by_layer"].append(round(clock() - t2, 2))
            for key, dt in (("wait_for_read", t1 - t0),
                            ("to_device", t2 - t1), ("forward", clock() - t2)):
                timing[key] = timing.get(key, 0.0) + dt
    out = {}
    for name, hs in (("exact", exact), ("int8", low)):
        picked = jnp.stack([hs[s, t] for s, t in rows])
        out[name] = np.asarray(
            family.logits_rows(client, config, picked), np.float32)
    return out


def _centred(x: np.ndarray) -> np.ndarray:
    return x - x.mean(axis=-1, keepdims=True)


def compare(client_logits: np.ndarray, ref: dict) -> dict:
    """Two numbers per row, each steady from seed to seed because it averages
    over the whole vocabulary instead of following one token:

    err   rms of (client - reference) over the rms of the reference's
          centred logits: how far the served logits are from float32.
    int8  projection of (client - reference) on (int8 reference - reference),
          in units of the latter: 0 when the served weights are the
          checkpoint's, 1 when they are its int8 codes. bfloat16 activations
          alone put `err` within a factor of two of an int8-weight server's,
          so `err` cannot tell the two apart; this can.
    """
    exact = _centred(ref["exact"])
    d = _centred(client_logits) - exact
    q = _centred(ref["int8"]) - exact
    scale = np.sqrt(np.mean(exact * exact, -1))
    return {"err": np.sqrt(np.mean(d * d, -1)) / scale,
            "int8": np.sum(d * q, -1) / np.sum(q * q, -1),
            # the control's own `err`: the int8 reference in the program's place
            "control_err": np.sqrt(np.mean(q * q, -1)) / scale}
