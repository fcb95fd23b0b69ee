#!/usr/bin/env python3
"""What the flash kernel takes at the cells' shapes, tile by tile, ON THE
CHIP (`flash_tiles`' budget and caps were set from these readings; without
a TPU the script refuses, exit code 2: a time or a share of the peak read
anywhere else is no reading, and the kernel's interpret-mode checks are
tests/test_flash_attention.py's):

    python3 scripts/flash_tile_readings.py [--other PATH/flash_attention.py]
        [--kernel PATH/flash_attention.py]
        [--cases trinity_win,...] [--tiles 512x128x6,...]
        [--forms split,single] [--out F]

For each case (one sequence's chunk at a cell's head geometry, the context a
traced chunk has) the rule's tile and every `--tiles` entry that divides the
shapes: milliseconds a call (ten chained calls in one program, the
transposes round the kernel included), the seconds the first call took
(trace, lower and compile: `first_s`), the share of the chip's 197 TFLOP/s
its causal / windowed FLOPs make, and the error against float64 numpy over
one K/V head's group. `--other` times another tree's kernel (the parent's)
at the same inputs; `--kernel` also times a variant of THIS tree's kernel
file (same interface: a body under trial) at each tile; `--forms single`
also times `p @ v` with the probabilities cast once to v's type (the
kernel's own form is `split`, two bfloat16 terms). One JSON object a line.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import importlib.util
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# name: (t, s, h, hkv, hd, window, start, float32 output)
CASES = {
    "trinity_win": (512, 5120, 48, 8, 128, 4096, 4400, False),
    # the run `_chunk_pages` gathered until PR 48: 37 x 128, `block_k` 128
    "trinity_win_4736": (512, 4736, 48, 8, 128, 4096, 4200, False),
    "trinity_full": (512, 16384, 48, 8, 128, 0, 10000, False),
    "qwen3next_full": (512, 16384, 16, 2, 256, 0, 10000, False),
    "phi4flash_win": (512, 1536, 40, 10, 128, 512, 900, True),
    "phi4flash_win_1152": (512, 1152, 40, 10, 128, 512, 620, True),
    "phi4flash_full": (512, 16384, 40, 10, 128, 0, 10000, True),
    "falconh1": (128, 4096, 20, 4, 128, 0, 3000, False),
    "qwen3moe": (128, 4096, 32, 4, 128, 0, 3000, False),
}
PEAK = 197e12


def _flops(t, h, hd, window, start):
    """FLOPs of the visible (query, key) pairs: two products of 2 * hd."""
    pairs = sum(
        min(start + i + 1, window or start + i + 1) for i in range(t)
    )
    return 4 * hd * h * pairs


def _reference(q, k, v, start, window, group):
    import numpy as np

    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    t, s = q.shape[1], k.shape[1]
    qg = q[0, :, :group]  # [t, group, hd] : K/V head 0's query heads
    logits = np.einsum("thd,sd->hts", qg, k[0, :, 0]) * q.shape[-1] ** -0.5
    pos = start + np.arange(t)[:, None]
    key = np.arange(s)[None]
    mask = key <= pos
    if window:
        mask &= key > pos - window
    logits = np.where(mask[None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,sd->thd", p, v[0, :, 0])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--other", default=None)
    parser.add_argument("--kernel", default=None)
    parser.add_argument("--cases", default=",".join(CASES))
    parser.add_argument("--tiles", default="")
    parser.add_argument("--forms", default="split")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("flash_tile_readings.py reads times on a TPU only; this "
              f"process has {jax.devices()[0].platform}", file=sys.stderr)
        return 2

    def load(name, path):
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    fa = importlib.import_module("bloombee_tpu.ops.pallas.flash_attention")
    other = load("other_fa", args.other) if args.other else None
    kernels = [("this", fa)]
    if args.kernel:
        kernels.append(("variant", load("variant_fa", args.kernel)))
    lines = []

    def emit(rec):
        lines.append(rec)
        print(json.dumps(rec), flush=True)

    def timed(fn, q, k, v, starts, lens):
        def chain(q, k, v, starts, lens):
            def body(_, q):
                return fn(q, k, v, starts=starts, lens=lens).astype(q.dtype)
            return jax.lax.fori_loop(0, 10, body, q)
        one = jax.jit(fn)
        ten = jax.jit(chain)
        t0 = time.perf_counter()
        out = jax.block_until_ready(one(q, k, v, starts=starts, lens=lens))
        first = time.perf_counter() - t0
        jax.block_until_ready(ten(q, k, v, starts, lens))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(ten(q, k, v, starts, lens))
            best = min(best, (time.perf_counter() - t0) / 10)
        return out, best * 1e3, first

    tiles = [tuple(int(x) for x in t.split("x"))
             for t in args.tiles.split(",") if t]
    for name in args.cases.split(","):
        t, s, h, hkv, hd, window, start, f32_out = CASES[name]
        n_rep = h // hkv
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
        q = jax.random.normal(keys[0], (1, t, h, hd), jnp.bfloat16)
        k = jax.random.normal(keys[1], (1, s, hkv, hd), jnp.bfloat16)
        v = jax.random.normal(keys[2], (1, s, hkv, hd), jnp.bfloat16)
        starts = jnp.array([start], jnp.int32)
        lens = starts + t
        ref = _reference(q, k, v, start, window, n_rep)
        flops = _flops(t, h, hd, window, start)
        kw = dict(causal=True, window=window)

        def report(what, tile, form, fn):
            try:
                out, ms, first = timed(fn, q, k, v, starts, lens)
            except Exception as e:  # a tile the compiler refuses
                emit({"case": name, "kernel": what, "tile": tile,
                      "form": form, "error": str(e)[:300]})
                return
            err = np.abs(
                np.asarray(out, np.float64)[0, :, :n_rep] - ref)
            emit({"case": name, "kernel": what, "tile": tile, "form": form,
                  "ms": round(ms, 4), "first_s": round(first, 2),
                  "peak_share": round(flops / (ms * 1e-3) / PEAK, 4),
                  "err_max": float(err.max()),
                  "err_mean": float(err.mean())})

        if other is not None:
            okw = dict(kw)
            if not window:
                okw.pop("window")
            report("other", [128, 128, 1], "float32", functools.partial(
                other.flash_attention.__wrapped__, **okw))
        rule = fa.flash_tiles(t, s, n_rep, hd, 2)
        for tile in [rule] + [x for x in tiles if x != rule]:
            bq, bk, g = tile
            if t % bq or s % bk or n_rep % g:
                continue
            for what, mod in kernels:
                for form in args.forms.split(","):
                    real, real_pv = mod.flash_tiles, mod._probs_times_v
                    mod.flash_tiles = lambda *a, _t=tile, **k: _t
                    if form == "single":  # p cast once to v's type
                        mod._probs_times_v = lambda p, v, _m=mod: _m._pv(
                            p.astype(v.dtype), v)
                    try:
                        report(
                            what, list(tile), form, functools.partial(
                                mod.flash_attention.__wrapped__,
                                out_dtype=jnp.float32 if f32_out else None,
                                **kw))
                    finally:
                        mod.flash_tiles = real
                        mod._probs_times_v = real_pv
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(
            "\n".join(json.dumps(x) for x in lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
