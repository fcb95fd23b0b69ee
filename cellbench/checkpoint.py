"""Seeded checkpoint writer (copied from chip_smoke.py, which stays as it is).

Writes an HF-layout directory (config.json + safetensors + index), one file a
layer and one for the client, from the PLAN of the configuration's family
(cellbench/families/<model_type>.py: each tensor's name, torch-layout shape
and fill; no tensor's name is known here). The default fill, "bits", comes
straight from the generator's bits: sign and mantissa random, magnitude over
four octaves 2**-9..2**-6 (std ~0.0137, HF's 0.02 init in spirit); "ones" is
1.0; a range is drawn as `fill_values` says. Every value is exactly a
bfloat16, so a bf16 server holds the reference's weights unrounded. JAX is
never imported here (the parent calls this).
"""

from __future__ import annotations

import json
import os
import pathlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from cellbench import families

CLIENT_SHARD = "client"
BITS, ONES = "bits", "ones"
_THEN = {
    None: lambda x: x,
    "log": np.log,
    # the x with log(1 + e**x) == y
    "softplus_inverse": lambda y: y + np.log(-np.expm1(-y)),
}


def layer_tag(layer: int) -> str:
    return f"layer{layer:03d}"


def tensor_plan(config: dict) -> list[tuple[str, list[tuple[str, tuple, object]]]]:
    """(file tag, [(tensor name, torch-layout shape, fill)]) per file: one
    per layer, one for the client, as the configuration's family gives them
    (a tensor without a fill gets BITS)."""
    family = families.of(config)
    files = [(layer_tag(layer), family.layer_tensors(config, layer))
             for layer in range(config["num_hidden_layers"])]
    files.append((CLIENT_SHARD, family.client_tensors(config)))
    return [(tag, [(t[0], tuple(t[1]), t[2] if len(t) > 2 else BITS)
                   for t in tensors]) for tag, tensors in files]


def fill_values(fill: dict, n: int, gen) -> np.ndarray:
    """`n` bfloat16 bit patterns of a range fill {"low", "high", "spacing":
    "uniform" | "log", "then": None | "log" | "softplus_inverse"}: drawn
    uniformly (or log-uniformly) in [low, high) from the file's generator,
    put through `then`, rounded to the nearest bfloat16. So a decay's
    `A_log` is {"low": 1, "high": 16, "spacing": "uniform", "then": "log"}
    and a step's bias {"low": 1e-3, "high": 1e-1, "spacing": "log", "then":
    "softplus_inverse"}."""
    u = (gen.random_raw(n) >> np.uint64(11)) * 2.0 ** -53
    low, high = float(fill["low"]), float(fill["high"])
    if fill.get("spacing", "uniform") == "log":
        x = np.exp(np.log(low) + u * (np.log(high) - np.log(low)))
    else:
        x = low + u * (high - low)
    bits = _THEN[fill.get("then")](x).astype(np.float32).view(np.uint32)
    bits = bits + (0x7FFF + ((bits >> 16) & 1))  # round to nearest even
    return (bits >> 16).astype(np.uint16)


def _slice(fill, m: int, gen) -> np.ndarray:
    """The next `m` bfloat16 bit patterns of a tensor with this fill."""
    if fill == ONES:
        return np.full(m, 0x3F80, np.uint16)  # 1.0
    if fill != BITS:
        return fill_values(fill, m, gen)
    bits = gen.random_raw(-(-m // 4)).view(np.uint16)[:m]
    exp = ((bits >> 7) & 3) + 118  # 2**-9 .. 2**-6
    exp <<= 7
    bits &= 0x807F
    bits |= exp
    return bits


def _write_file(path: pathlib.Path, tensors, seed_seq) -> int:
    header, offset = {}, 0
    for name, shape, _fill in tensors:
        nbytes = 2 * int(np.prod(shape))
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [offset, offset + nbytes]}
        offset += nbytes
    head = json.dumps(header, separators=(",", ":")).encode()
    head += b" " * (-len(head) % 8)
    gen = np.random.PCG64(seed_seq)
    step = 1 << 22  # bf16 values per slice
    tmp = path.with_suffix(".tmp")
    with open(tmp, "wb") as f:
        f.write(len(head).to_bytes(8, "little"))
        f.write(head)
        for _name, shape, fill in tensors:
            n = int(np.prod(shape))
            for start in range(0, n, step):
                f.write(_slice(fill, min(step, n - start), gen).tobytes())
    os.replace(tmp, path)
    return offset


def file_name(tag: str) -> str:
    return f"model-{tag}.safetensors"


def write_checkpoint(path: pathlib.Path, config: dict, seed: int,
                     only: str | None = None, workers: int = 8) -> dict:
    """Write the layer files (only="layers"), the client's file
    (only="client") or both, plus config.json and the index. The two halves
    can be written at different times: the server reads only layer files."""
    t0 = time.time()
    path.mkdir(parents=True, exist_ok=True)
    files = tensor_plan(config)
    seeds = np.random.SeedSequence(seed).spawn(len(files))
    jobs = [
        (tag, tensors, s) for (tag, tensors), s in zip(files, seeds)
        if only is None or (only == "client") == (tag == CLIENT_SHARD)
    ]
    weight_map = {t[0]: file_name(tag) for tag, ts in files for t in ts}
    (path / "model.safetensors.index.json").write_text(
        json.dumps({"metadata": {}, "weight_map": weight_map})
    )
    (path / "config.json").write_text(json.dumps(config, indent=1))
    with ThreadPoolExecutor(max_workers=max(1, workers)) as pool:
        sizes = list(pool.map(
            lambda j: _write_file(path / file_name(j[0]), j[1], j[2]), jobs
        ))
    return {"seconds": round(time.time() - t0, 2), "bytes": int(sum(sizes)),
            "files": len(jobs)}
