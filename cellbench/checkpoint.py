"""Seeded checkpoint writer (copied from chip_smoke.py, which stays as it is).

Writes an HF-layout directory (config.json + safetensors + index) under the
names bloombee_tpu/models/checkpoint.py reads, straight from the generator's
bits: sign and mantissa random, magnitude over four octaves 2**-9..2**-6
(std ~0.0137, HF's 0.02 init in spirit); norm weights are ones. Every value
is exactly a bfloat16, so a bf16 server holds the reference's weights
unrounded. JAX is never imported here (the parent calls this).
"""

from __future__ import annotations

import json
import os
import pathlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CLIENT_SHARD = "client"


def tensor_plan(config: dict) -> list[tuple[str, list[tuple[str, tuple]]]]:
    """(file tag, [(tensor name, torch-layout shape)]) per file: one per
    layer, one for the client's trio (embed, final norm, head)."""
    d = config["hidden_size"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or d // heads
    moe = config["model_type"] == "qwen3_moe"
    files = []
    for layer in range(config["num_hidden_layers"]):
        p = f"model.layers.{layer}"
        tensors = [
            (f"{p}.input_layernorm.weight", (d,)),
            (f"{p}.post_attention_layernorm.weight", (d,)),
            (f"{p}.self_attn.q_proj.weight", (heads * hd, d)),
            (f"{p}.self_attn.k_proj.weight", (kv_heads * hd, d)),
            (f"{p}.self_attn.v_proj.weight", (kv_heads * hd, d)),
            (f"{p}.self_attn.o_proj.weight", (d, heads * hd)),
        ]
        if moe:
            i = config["moe_intermediate_size"]
            tensors += [
                (f"{p}.self_attn.q_norm.weight", (hd,)),
                (f"{p}.self_attn.k_norm.weight", (hd,)),
                (f"{p}.mlp.gate.weight", (config["num_experts"], d)),
            ]
            for e in range(config["num_experts"]):
                q = f"{p}.mlp.experts.{e}"
                tensors += [
                    (f"{q}.gate_proj.weight", (i, d)),
                    (f"{q}.up_proj.weight", (i, d)),
                    (f"{q}.down_proj.weight", (d, i)),
                ]
        else:
            i = config["intermediate_size"]
            tensors += [
                (f"{p}.mlp.gate_proj.weight", (i, d)),
                (f"{p}.mlp.up_proj.weight", (i, d)),
                (f"{p}.mlp.down_proj.weight", (d, i)),
            ]
        files.append((f"layer{layer:03d}", tensors))
    v = config["vocab_size"]
    files.append((CLIENT_SHARD, [
        ("model.embed_tokens.weight", (v, d)),
        ("model.norm.weight", (d,)),
        ("lm_head.weight", (v, d)),
    ]))
    return files


def _write_file(path: pathlib.Path, tensors, seed_seq) -> int:
    header, offset = {}, 0
    for name, shape in tensors:
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
        for name, shape in tensors:
            n = int(np.prod(shape))
            if name.endswith("norm.weight"):
                f.write(np.full(n, 0x3F80, np.uint16).tobytes())  # 1.0
                continue
            for start in range(0, n, step):
                m = min(step, n - start)
                bits = gen.random_raw(-(-m // 4)).view(np.uint16)[:m]
                exp = ((bits >> 7) & 3) + 118  # 2**-9 .. 2**-6
                exp <<= 7
                bits &= 0x807F
                bits |= exp
                f.write(bits.tobytes())
    os.replace(tmp, path)
    return offset


def file_name(tag: str) -> str:
    return f"model-{tag}.safetensors"


def write_checkpoint(path: pathlib.Path, config: dict, seed: int,
                     only: str | None = None, workers: int = 8) -> dict:
    """Write the layer files (only="layers"), the client trio
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
