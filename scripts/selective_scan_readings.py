#!/usr/bin/env python3
"""What the selective-scan kernel takes ALONE at the cell
`phi4flash-longctx`'s shapes, ON THE CHIP (`scan_block_c`'s one register a
state column was held against these readings in PR 59; run by no cell;
without a TPU the script refuses, exit code 2: a time read anywhere else is
no reading, and the kernel's interpret-mode checks are
tests/test_phi4flash.py's):

    python3 scripts/selective_scan_readings.py [--rows 512,8]
        [--block-c 256,512,1024] [--block-t 64,128,256] [--out F]
        [--other PATH/selective_scan.py]

For each count of rows (512: a chunk; 8: the tail program's one tile, five
rows real) at the published widths (C 5120 channels, N 16 state columns, a
state that is not empty) and each (block_c, block_t) that divides the
shapes: nine calls in one jit, each from the state the one before it left (a
chunk of the cell runs nine Mamba-1 layers), microseconds a call,
nanoseconds a token and channel tile, the time
`cellbench/families/phi4flash.py` `mamba1_scan_needs`' bytes of ONE layer
take at 819 GB/s and that time's share of the call, the seconds the first
call took (trace, lower and compile: `first_s`), and the largest
difference of y and of the final state from `ops/ssm.py` `mamba1_chunk` on
the same chip. The tile `scan_block_c` gives the shape, at the wrapper's own
block_t, is marked `rule`. `--other` times another tree's kernel file too
(the parent's: `"kernel": "other"`), at its own default tile and at each
listed (block_c, block_t). One JSON object a line, on standard
output and, as it is read, in `--out`.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import inspect
import itertools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

HBM_BYTES_PER_S = 819e9  # one v5e chip (Google Cloud documentation, "TPU v5e")
CONFIG = ROOT / "cellbench" / "configs" / "phi4-mini-flash-full32.json"
CALLS, REPEATS = 9, 4


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", default="512,8")
    parser.add_argument("--block-c", default="256,512,1024")
    parser.add_argument("--block-t", default="64,128,256")
    parser.add_argument("--out", default=None)
    parser.add_argument("--other", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("selective_scan_readings.py reads times on a TPU only; this "
              f"process has {jax.devices()[0].platform}", file=sys.stderr)
        return 2

    from bloombee_tpu.ops.pallas import selective_scan as this
    from bloombee_tpu.ops.ssm import mamba1_chunk
    from cellbench.families.phi4flash import dims, mamba1_scan_needs

    config = json.loads(CONFIG.read_text())
    m = dims(config)
    ch, n = m["inner"], m["state"]
    layers = mamba1_scan_needs(config, 1, "chunk")["flops"] // (6 * ch * n)
    kernels = [("this", this)]
    if args.other:
        spec = importlib.util.spec_from_file_location("other_ss", args.other)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        kernels.append(("other", other))
    sinks = [sys.stdout]

    def emit(rec):
        for f in sinks:
            print(json.dumps(rec), file=f, flush=True)

    def inputs(rows, real):
        rng = np.random.default_rng(59 + rows)
        f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
        dt = rng.uniform(1e-3, 0.2, (rows, ch)).astype(np.float32)
        dt[real:] = 0.0  # the tail's pad rows
        return tuple(jnp.asarray(z) for z in (
            f(rows, ch), dt, -rng.uniform(0.05, 4, (n, ch)).astype(np.float32),
            f(rows, n), f(rows, n), f(ch), f(n, ch)))

    def timed(fn, operands):
        """(y, S, the first call's seconds, seconds a call in a chain)."""
        t0 = time.perf_counter()
        y, s = jax.block_until_ready(jax.jit(fn)(*operands))
        first = time.perf_counter() - t0

        def chain(x, dt, a, b, c, d, s):
            # the calls hang on one another through the STATE alone (80 KB):
            # nothing elementwise over [T, C] stands between two of them
            def body(_, carry):
                s, y = carry
                for _ in range(CALLS):
                    y, s = fn(x, dt, a, b, c, d, s)
                return s, y
            return jax.lax.fori_loop(0, REPEATS, body, (s, x))

        many = jax.jit(chain)
        jax.block_until_ready(many(*operands))
        best = 1e9
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(many(*operands))
            best = min(best, time.perf_counter() - t0)
        return np.asarray(y), np.asarray(s), first, best / (CALLS * REPEATS)

    with contextlib.ExitStack() as stack:
        if args.out:
            pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            sinks.append(stack.enter_context(open(args.out, "w")))
        for rows in _ints(args.rows):
            operands = inputs(rows, 5 if rows == 8 else rows)
            want_y, want_s = (np.asarray(z) for z in jax.block_until_ready(
                jax.jit(mamba1_chunk)(*operands)))
            bytes_us = (mamba1_scan_needs(config, rows, "chunk")["bytes"]
                        / layers / HBM_BYTES_PER_S * 1e6)
            tiles = [(None, None)] + list(itertools.product(
                _ints(args.block_c), _ints(args.block_t)))
            for (block_c, block_t), (name, mod) in itertools.product(
                    tiles, kernels):
                defaults = {
                    k: p.default for k, p in inspect.signature(
                        mod.selective_scan.__wrapped__).parameters.items()}
                bc = block_c or defaults["block_c"] or this.scan_block_c(ch)
                bt = block_t or min(defaults["block_t"], -(-rows // 8) * 8)
                if ch % bc or rows % bt:
                    continue  # no tile of this shape
                rec = {"kernel": name, "rows": rows, "block_c": bc,
                       "block_t": bt,
                       "rule": name == "this" and block_c is None}
                try:
                    y, s, first, sec = timed(
                        lambda *a, mod=mod: mod.selective_scan(
                            *a, block_c=bc, block_t=bt), operands)
                except Exception as e:  # a tile the compiler refuses
                    emit({**rec, "error": str(e)[:300]})
                    continue
                steps = -(-rows // 8) * 8 * (ch // bc)
                emit({**rec, "us": round(sec * 1e6, 2),
                      "ns_token_tile": round(sec * 1e9 / steps, 2),
                      "bytes_us": round(bytes_us, 2),
                      "bytes_share": round(bytes_us / (sec * 1e6), 4),
                      "first_s": round(first, 2),
                      "diff_y": float(np.abs(y - want_y).max()),
                      "diff_s": float(np.abs(s - want_s).max())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
