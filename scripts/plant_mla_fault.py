#!/usr/bin/env python3
"""Plant a latent-attention or routing fault in a COPY of the program, to see
that the benchmark's `correct` notices it (PERF.md section 2: the controls of
`deepseek-v2-ep8-span5`'s `logit_error_limit`).

    python3 scripts/plant_mla_fault.py rope_key .try_fault_rope
    cd .try_fault_rope && python3 cellbench/run.py --workload \\
        deepseekv2-longctx --seed 3500000403 --seconds 15 --trace 0
    # correct: false

The copy holds `bloombee_tpu/`, `cellbench/` and `BENCHMARK.json` (all
`cellbench/run.py` needs) with ONE line of the program changed:

  rope_key     the rotary key is written to the cache as zeros: every score
               loses its positional part (runtime/layer_body.py)
  route_scale  `routed_scaling_factor` left out of the router's weights: the
               routed experts count a sixteenth (models/deepseek_v2.py)
  routed_sum   the held experts' partial sum dropped: a sparse layer gives
               its shared experts' output alone (runtime/layer_body.py)

The served tokens still come and no request fails; only the comparison with
the reference sees it. `.try*` is in `.gitignore`.
"""

from __future__ import annotations

import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BODY = pathlib.Path("bloombee_tpu") / "runtime" / "layer_body.py"
FAMILY = pathlib.Path("bloombee_tpu") / "models" / "deepseek_v2.py"
FAULTS = {
    "rope_key": (
        BODY,
        "        c_slab, pe_slab = arena_write(c_slab, pe_slab, slots, c_kv, k_pe)",
        "        c_slab, pe_slab = arena_write(c_slab, pe_slab, slots, c_kv, k_pe * 0)",
    ),
    "route_scale": (
        FAMILY,
        '        moe_route_scale=float(get("routed_scaling_factor", 1.0)),',
        "        moe_route_scale=1.0,",
    ),
    "routed_sum": (
        BODY,
        "                out = out + shared.astype(out.dtype)",
        "                out = shared.astype(out.dtype)",
    ),
}


def plant(tree: pathlib.Path, kind: str) -> None:
    """Change the one line of `tree`'s program that `kind` names."""
    where, sound, broken = FAULTS[kind]
    path = tree / where
    text = path.read_text()
    if text.count(sound) != 1:
        raise SystemExit(f"{path}: expected the sound line once: {sound!r}")
    path.write_text(text.replace(sound, broken))


def main(argv: list[str]) -> int:
    kind, tree = argv[0], pathlib.Path(argv[1])
    shutil.rmtree(tree, ignore_errors=True)
    tree.mkdir(parents=True)
    junk = shutil.ignore_patterns("__pycache__")
    for name in ("bloombee_tpu", "cellbench"):
        shutil.copytree(ROOT / name, tree / name, ignore=junk)
    shutil.copy(ROOT / "BENCHMARK.json", tree / "BENCHMARK.json")
    plant(tree, kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
