#!/usr/bin/env python3
"""Plant a recurrent-state fault in a COPY of the program, to see that the
benchmark's `correct` notices it (PERF.md section 2: the controls of
`falcon-h1-34b-span8`'s `logit_error_limit`).

    python3 scripts/plant_state_fault.py pad   .try_fault_pad
    cd .try_fault_pad && python3 cellbench/run.py --workload falconh1-longdoc \\
        --seed 3012000403 --seconds 15 --trace 0      # correct: false

The copy holds `bloombee_tpu/`, `cellbench/` and `BENCHMARK.json` (all
`cellbench/run.py` needs) with ONE line of `runtime/layer_body.py` changed:

  pad    a chunk's bucket tail feeds the state S (the mask on dt taken off):
         padding rows advance the state
  reset  a prompt's last chunk (its tail, not a multiple of the chunk length)
         starts from an empty S: the state is lost at the last chunk boundary

The served tokens still come and no request fails; only the comparison with
the reference sees it. `.try*` is in `.gitignore`.
"""

from __future__ import annotations

import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BODY = pathlib.Path("bloombee_tpu") / "runtime" / "layer_body.py"
FAULTS = {
    "pad": (
        "valid = jnp.arange(w, dtype=jnp.int32) < n_c",
        "valid = jnp.arange(w, dtype=jnp.int32) < jnp.where(n_c > 0, w, 0)",
    ),
    "reset": (
        "            s0 = jnp.where(rows.fresh[c], 0.0, s0)",
        "            s0 = jnp.where(rows.fresh[c] | (n_c % 128 != 0), 0.0, s0)",
    ),
}


def plant(tree: pathlib.Path, kind: str) -> None:
    """Change the one line of `tree`'s layer body that `kind` names."""
    sound, broken = FAULTS[kind]
    body = tree / BODY
    text = body.read_text()
    if text.count(sound) != 1:
        raise SystemExit(f"{body}: expected the sound line once: {sound!r}")
    body.write_text(text.replace(sound, broken))


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
