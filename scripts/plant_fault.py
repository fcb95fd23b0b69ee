#!/usr/bin/env python3
"""Plant one of a family's mechanisms broken in a COPY of the program, to see
that the benchmark's `correct` notices it (PERF.md section 2: the controls of
each configuration's `logit_error_limit`).

    python3 scripts/plant_fault.py afmoe bias_choice .try_fault
    cd .try_fault && python3 cellbench/run.py --workload trinity-longctx \\
        --seed 4700000403 --seconds 0 --trace 0        # correct: false

The copy holds `bloombee_tpu/`, `cellbench/` and `BENCHMARK.json` (all
`cellbench/run.py` needs) with ONE line of the program changed: `FAULTS`
below, a table a family (the family's cell beside its name), one row a
fault: what it breaks, the file, the sound line, the broken line. The served
tokens still come and no request fails; only the comparison with the
reference sees it. `.try*` is in `.gitignore`.
`tests/test_cell_rehearsal.py` plants them under the CPU rehearsal, and
checks in a second that every sound line is still in the program once.
"""

from __future__ import annotations

import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
PROGRAM = pathlib.Path("bloombee_tpu")
BODY = PROGRAM / "runtime" / "layer_body.py"
STEP = PROGRAM / "runtime" / "step.py"
SAMBAY = PROGRAM / "runtime" / "sambay.py"
MOE = PROGRAM / "ops" / "moe.py"
DEEPSEEK_V2 = PROGRAM / "models" / "deepseek_v2.py"
AFMOE = PROGRAM / "models" / "afmoe.py"
KIMI_LINEAR = PROGRAM / "models" / "kimi_linear.py"
NEMOTRON_H = PROGRAM / "models" / "nemotron_h.py"

ROUTED_SUM = (
    BODY,
    "                out = out + shared.astype(out.dtype)",
    "                out = shared.astype(out.dtype)",
)
# the delta-rule mixer's lines that qwen3_next and kimi_linear share
QWEN3_NEXT_STATE_RESET = (
    "a chunk with a padded tail (a prompt's LAST chunk) starts from an empty "
    "state S: what the linear layers kept of the prompt before that chunk "
    "boundary is lost",
    BODY,
    "            s0 = jnp.where(rows.fresh[c], 0.0, s0)  # gdn",
    "            s0 = jnp.where(rows.fresh[c] | (n_c < w), 0.0, s0)  # gdn",
)
QWEN3_NEXT_BETA = (
    "beta left out of the update (taken as 1): every token overwrites what "
    "its key reads instead of blending into it",
    BODY,
    "        beta = jax.nn.sigmoid(b)",
    "        beta = jnp.ones_like(b)",
)
# the sigmoid router's line that afmoe and kimi_linear share
AFMOE_BIAS_CHOICE = (
    "the router's bias (`expert_bias`) left out of its choice: the top-k of "
    "the unbiased scores",
    MOE,
    "            scores if bias is None else scores + bias.astype(jnp.float32),",
    "            scores,",
)

FAULTS = {
    # falconh1-longdoc
    "falcon_h1": {
        "pad": (
            "a chunk's bucket tail feeds the state S (the mask on dt taken "
            "off): padding rows advance the state",
            BODY,
            "            valid = jnp.arange(w, dtype=jnp.int32) < n_c",
            "            valid = jnp.arange(w, dtype=jnp.int32) < jnp.where(n_c > 0, w, 0)",
        ),
        "reset": (
            "a prompt's last chunk (its tail, not a multiple of the chunk "
            "length) starts from an empty S",
            BODY,
            "            s0 = jnp.where(rows.fresh[c], 0.0, s0)",
            "            s0 = jnp.where(rows.fresh[c] | (n_c % 128 != 0), 0.0, s0)",
        ),
    },
    # deepseekv2-longctx
    "deepseek_v2": {
        "rope_key": (
            "the rotary key is written to the cache as zeros: every score "
            "loses its positional part",
            BODY,
            "        c_slab, pe_slab = arena_write(c_slab, pe_slab, slots, c_kv, k_pe)",
            "        c_slab, pe_slab = arena_write(c_slab, pe_slab, slots, c_kv, k_pe * 0)",
        ),
        "route_scale": (
            "`routed_scaling_factor` left out of the router's weights: the "
            "routed experts count a sixteenth",
            DEEPSEEK_V2,
            '        moe_route_scale=float(get("routed_scaling_factor", 1.0)),',
            "        moe_route_scale=1.0,",
        ),
        "routed_sum": (
            "the held experts' partial sum dropped: a sparse layer gives its "
            "shared experts' output alone",
            *ROUTED_SUM,
        ),
    },
    # qwen3next-longctx
    "qwen3_next": {
        "state_reset": QWEN3_NEXT_STATE_RESET,
        "beta": QWEN3_NEXT_BETA,
        "attn_gate": (
            "the full-attention layers' output gate left out",
            BODY,
            "            attn = attn * jax.nn.sigmoid(gate).astype(attn.dtype)",
            "            attn = attn * 1",
        ),
    },
    # phi4flash-longctx
    "phi4flash": {
        "cross_row": (
            "the cross layers read ANOTHER row of the K/V arena (the first "
            "window layer's) instead of the full layer's",
            SAMBAY,
            "        shared_pages = layer_pages(page_table, row, num_pages)",
            "        shared_pages = layer_pages(page_table, (row + 1) % kv_layers,"
            " num_pages)",
        ),
        "memory": (
            "the gated memory units read zeros instead of the last Mamba "
            "layer's scan output",
            SAMBAY,
            "                h_c = _layer(spec, gmu_l, h_c, x, _gmu(gmu_l, x, m_c))",
            "                h_c = _layer(spec, gmu_l, h_c, x, _gmu(gmu_l, x, m_c * 0))",
        ),
        "lambda": (
            "lambda left out of differential attention (taken as 0: the "
            "second softmax is never subtracted)",
            SAMBAY,
            "        o = a[:, :, 0] - lam * a[:, :, 1]",
            "        o = a[:, :, 0] - 0 * lam * a[:, :, 1]",
        ),
        "state_reset": (
            "a chunk with a padded tail (a prompt's LAST chunk) starts from "
            "an empty state S: what the Mamba layers kept of the prompt "
            "before that chunk boundary is lost",
            SAMBAY,
            "            s0 = jnp.where(rows.fresh[q], 0.0, s0)",
            "            s0 = jnp.where(rows.fresh[q] | (n_q < w), 0.0, s0)",
        ),
    },
    # trinity-longctx
    "afmoe": {
        "bias_choice": AFMOE_BIAS_CHOICE,
        "biased_weights": (
            "the weights taken from the BIASED scores of the chosen experts",
            MOE,
            "        weights = jnp.take_along_axis(scores, idx, axis=-1)",
            "        weights = jnp.take_along_axis(scores + bias, idx, axis=-1)",
        ),
        "route_scale": (
            "`route_scale` left out of the router's weights: the routed "
            "experts count 1 / 2.448",
            AFMOE,
            '        moe_route_scale=float(get("route_scale", 1.0)),',
            "        moe_route_scale=1.0,",
        ),
        "full_rope": (
            "rotary applied in the full layers too",
            STEP,
            "        cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)",
            "        cos, sin = cos_loc, sin_loc",
        ),
        "window": (
            "the window ignored: a window layer attends every cached token, "
            "its rotary kept",
            STEP,
            "            slots_l, pages_l, q_positions, total_lens, tm, window_l,",
            "            slots_l, pages_l, q_positions, total_lens, tm, window_l * 0,",
        ),
        "attn_gate": (
            "the attention's output gate left out",
            AFMOE,
            "        attn_gate=True,",
            "        attn_gate=False,",
        ),
        "routed_sum": (
            "the held experts' partial sum dropped: a sparse layer gives its "
            "shared expert's output alone",
            *ROUTED_SUM,
        ),
        "embed_scale": (
            "sqrt(hidden_size) left off the client's embedding rows",
            AFMOE,
            '            math.sqrt(config.hidden_size) if get("mup_enabled", False)',
            '            1.0 if get("mup_enabled", False)',
        ),
    },
    # kimilinear-longctx
    "kimi_linear": {
        "scalar_decay": (
            "a head's decay replaced by its mean over the 128 key channels: "
            "the program computes Gated DeltaNet",
            BODY,
            '        g = -jnp.exp(params["gdn_a_log"]).reshape(shape) * dt',
            '        g = -jnp.exp(params["gdn_a_log"]).reshape(shape) * ('
            "dt * 0 + dt.mean(-1, keepdims=True))",
        ),
        "beta_out": QWEN3_NEXT_BETA,
        "state_reset": QWEN3_NEXT_STATE_RESET,
        "rope_in_full": (
            "rotary applied to the 64 shared-key columns of the latent "
            "layers' queries and keys (`mla_use_nope` ignored)",
            BODY,
            "        if not mla.rope:",
            "        if False:",
        ),
        "gate_out": (
            "the KDA mixer's sigmoid output gate left out",
            BODY,
            "        gate = jax.nn.sigmoid if gdn.gate_rank else jax.nn.silu",
            "        gate = jnp.ones_like if gdn.gate_rank else jax.nn.silu",
        ),
        "route_scale_out": (
            "`routed_scaling_factor` left out of the router's weights: the "
            "routed experts count 1 / 2.446",
            KIMI_LINEAR,
            '        moe_route_scale=float(get("routed_scaling_factor", 1.0)),',
            "        moe_route_scale=1.0,",
        ),
        "bias_out": AFMOE_BIAS_CHOICE,
    },
    # nemotron3nano-longctx
    "nemotron_h": {
        "state_reset": (
            "a chunk with a padded tail (a prompt's LAST chunk) starts from "
            "an empty state S: what the Mamba-2 layers kept of the prompt "
            "before that chunk boundary is lost",
            BODY,
            "            s0 = jnp.where(rows.fresh[c], 0.0, s0)",
            "            s0 = jnp.where(rows.fresh[c] | (n_c < w), 0.0, s0)",
        ),
        "relu2_silu": (
            "relu(u) ** 2 replaced by silu(u) in the ROUTED experts (every "
            "form: the dense einsums and both kernels share the line)",
            MOE,
            "    return relu2(u)",
            "    return jax.nn.silu(u)",
        ),
        "route_scale_out": (
            "`routed_scaling_factor` left out of the router's weights: the "
            "routed experts count 1 / 2.5",
            NEMOTRON_H,
            '        moe_route_scale=float(get("routed_scaling_factor", 1.0)),',
            "        moe_route_scale=1.0,",
        ),
        "bias_out": AFMOE_BIAS_CHOICE,
    },
}


def target(tree: pathlib.Path, family: str, fault: str):
    """(file, its text, the sound line, the broken line, how often the sound
    LINE stands in the text: 1 for a planter that has not drifted)."""
    _what, where, sound, broken = FAULTS[family][fault]
    text = (tree / where).read_text()
    return tree / where, text, sound, broken, text.count(sound + "\n")


def plant(tree: pathlib.Path, family: str, fault: str) -> pathlib.Path:
    """Change the one line of `tree`'s program that (family, fault) names;
    the file it changed."""
    path, text, sound, broken, found = target(tree, family, fault)
    if found != 1:
        raise SystemExit(f"{path}: expected the sound line once: {sound!r}")
    path.write_text(text.replace(sound + "\n", broken + "\n"))
    return path


def main(argv: list[str]) -> int:
    if len(argv) != 3 or argv[1] not in FAULTS.get(argv[0], ()):
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        for family, faults in FAULTS.items():
            for fault, (what, *_) in faults.items():
                print(f"  {family} {fault}: {what}", file=sys.stderr)
        return 2
    family, fault, tree = argv[0], argv[1], pathlib.Path(argv[2])
    shutil.rmtree(tree, ignore_errors=True)
    tree.mkdir(parents=True)
    junk = shutil.ignore_patterns("__pycache__")
    for name in ("bloombee_tpu", "cellbench"):
        shutil.copytree(ROOT / name, tree / name, ignore=junk)
    shutil.copy(ROOT / "BENCHMARK.json", tree / "BENCHMARK.json")
    plant(tree, family, fault)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
