"""Stored layouts of stacked weights: a span's weights lie on the device the
way its step programs read them, so no step holds a copy of a parameter.

The TPU compiler reads each layer's q/k/v weight with the INPUT dimension
minor. Stored `[L, in, out]`, a span-step program began with a `copy` of
the whole stack into that layout: 2.5 ms of a 14.6 ms Mistral chunk, every
chunk run (PERF.md section 6, PR 34). Stored `[L, out, in]`, which is how
the checkpoint holds them (torch `[out, in]`), the same matmul reads them
where they lie. The other projections (`o_proj`, `gate/up/down_proj`, the expert
stacks, `ssm_out_proj`) are read as `[L, in, out]` without a copy and stay.

A stack whose minor dimension is not a multiple of the 128-lane vector
width is re-laid out the same way; Falcon-H1's `ssm_in_proj` (9248 columns)
is the one such stack (2.3 ms of EVERY run of a 12.6 ms step), and is stored
with zero columns up to `lane_padded` (models/falcon_h1.py; the mixer reads
`[:, :proj_dim]` of the product).

The rule for a new family's loader: read every projection through
`checkpoint.read_weight(reader, name, key)`, which leaves an output-major
key as the checkpoint has it and transposes the rest; apply it through
`project`. Which keys are output-major is this one constant: a square
`q_proj` cannot be told from its transpose by shape.
"""

from __future__ import annotations

# stored [out, in] (stacked [L, out, in]); every other projection [in, out]
OUT_MAJOR_KEYS = frozenset((
    "q_proj", "k_proj", "v_proj",
    # latent attention's projections out of the hidden rows and the query's
    # up-projection: as the checkpoint has them, like q/k/v
    "q_a_proj", "q_b_nope", "q_b_rope", "kv_a_proj", "router_t",
    # kv_b's two halves [heads, dim, kv_rank]: the checkpoint's rows
    "kv_b_k", "kv_b_v",
    # a gated attention's gate rows, cut out of q_proj at load (qwen3_next)
    "q_gate_proj",
))

LANES = 128  # the TPU's vector width: a minor dimension is tiled by it

# A span whose first layers are of another KIND of MLP than the rest (a
# dense layer before sparse ones: deepseek_v2) is stored as two stacks in
# the one params dict: the leading run's leaves under this prefix, the
# rest under the plain keys. Each run is scanned on its own (runtime/step.py)
# with the hidden rows and the one flat arena carried through both; a span
# of one kind has no such keys and is what it always was.
LEAD = "lead."


# A span whose layer kinds INTERLEAVE with a period (qwen3_next,
# kimi_linear: linear, linear, linear, full) is stored as one stack a
# POSITION in the period:
# the j-th linear layer of every period under `linear_prefix(j)`, the full
# layers under the plain keys, each [periods, ...]. The step scans PERIODS
# and runs a period's layers in order (runtime/step.py `_scan_periods`),
# each stack an xs of its own: ONE stack [periods, 3, ...] for the linear
# kind made every period copy its three layers out of it before the first
# ran (2.4 GB of experts at qwen3-next's widths: the slice has three
# consumers and is no view of any). Where one period differs from the rest
# (kimi_linear: the model's leading dense layer stands in the first, the
# last is short) the span is two runs of like periods, the first's stacks
# under `LEAD` besides (`lead.lin0.gate_proj`), each run a scan of its own.
LINEAR = "lin"


def linear_prefix(j: int) -> str:
    return f"{LINEAR}{j}."


# A SambaY span (phi4flash) is up to three RUNS of (mixer, attention) pairs,
# each of another pair of kinds: "a" (mamba, window attention) pairs, "b" the
# one (mamba, full attention) pair whose scan output and K/V pages the
# cross-decoder reads, "c" (gated memory unit, cross attention) pairs. One
# stack a (run, position): `sambay_prefix("a", 0)` the mamba layers of run
# a, [pairs, ...], and so on; runtime/sambay.py scans a run's pairs.
SAMBAY_RUNS = ("a", "b", "c")


def sambay_prefix(run: str, position: int) -> str:
    return f"s{run}{position}."


def split_sambay(stacked: dict) -> dict[str, tuple[dict, dict]]:
    """{run: (the mixers' stack, the attention layers' stack)} under plain
    keys, for the runs the span holds; {} for any other span."""
    out = {}
    for run in SAMBAY_RUNS:
        pair = tuple(
            {k[len(sambay_prefix(run, j)):]: w for k, w in stacked.items()
             if k.startswith(sambay_prefix(run, j))}
            for j in (0, 1)
        )
        if pair[0]:
            out[run] = pair
    return out


# A span of layers that are ONE sublayer each (nemotron_h) is a LIST of runs
# of a repeated unit of kinds, as many as the pattern has
# (`ModelSpec.period_runs`: a period that repeats, or ((moe, mamba) x 3,
# (full,) x 1, ...) for a period that stands alone): one stack
# a (run, position in the unit), `unit_prefix(r, j)`, each [repeats, ...].
# The step scans a run's repeats and runs a unit's layers in order, the
# same scan as the periods above (`period_stacks` hands it either layout).
def unit_prefix(run: int, position: int) -> str:
    return f"r{run}p{position}."


def _unit_of(key: str) -> tuple[int, int] | None:
    """(run, position) of a key under a `unit_prefix`, None for any other."""
    head, dot, _ = key.partition(".")
    run, p, position = head[1:].partition("p")
    if dot and head[:1] == "r" and p and run.isdigit() and position.isdigit():
        return int(run), int(position)
    return None


def period_stacks(stacked: dict) -> list[list[dict]]:
    """A span whose kinds interleave as the step scans it: a list of runs,
    each the list of its positions' stacks under plain keys, every leaf
    [repeats, ...]. From either stored layout: `unit_prefix` keys, or the
    periods' (`LEAD`, `linear_prefix`, the closing layers' plain keys)."""
    units = {k: _unit_of(k) for k in stacked}
    if any(u is not None for u in units.values()):
        runs: dict[int, dict[int, dict]] = {}
        for key, (r, j) in units.items():
            runs.setdefault(r, {}).setdefault(j, {})[
                key.partition(".")[2]] = stacked[key]
        return [
            [runs[r][j] for j in sorted(runs[r])] for r in sorted(runs)
        ]
    lead, main = split_runs(stacked)
    out = []
    for run in ([main] if lead is None else [lead, main]):
        linear, full = split_kinds(run)
        out.append([*linear, full])
    return out


def plain_key(key: str) -> str:
    """A stacked dict's key without its run's and its position's prefix."""
    if _unit_of(key) is not None:
        return key.partition(".")[2]
    if key.startswith(LEAD):
        key = key[len(LEAD):]
    head, dot, rest = key.partition(".")
    if dot and len(head) == 3 and head[0] == "s" and head[1] in SAMBAY_RUNS \
            and head[2] in "01":
        return rest
    if dot and head.startswith(LINEAR) and head[len(LINEAR):].isdigit():
        return rest
    return key


def split_kinds(stacked: dict) -> tuple[list[dict], dict]:
    """([the j-th linear layers' stack for j = 0 ..], the full layers'
    stack), all under plain keys, of a span stored by position in its
    period; ([], stacked) for any other span."""
    linear, j = [], 0
    while any(k.startswith(linear_prefix(j)) for k in stacked):
        n = len(linear_prefix(j))
        linear.append({
            k[n:]: w for k, w in stacked.items()
            if k.startswith(linear_prefix(j))
        })
        j += 1
    if not linear:
        return [], stacked
    return linear, {k: w for k, w in stacked.items() if plain_key(k) == k}


def split_runs(stacked: dict) -> tuple[dict | None, dict]:
    """(the leading run's stack under plain keys or None, the main stack)."""
    lead = {k[len(LEAD):]: w for k, w in stacked.items() if k.startswith(LEAD)}
    if not lead:
        return None, stacked
    return lead, {k: w for k, w in stacked.items() if not k.startswith(LEAD)}


def stacked_layers(stacked: dict) -> int:
    """How many layers a stacked params dict holds, every run and kind."""
    import jax

    runs = split_sambay(stacked)
    if runs:
        return sum(
            2 * jax.tree.leaves(mixers)[0].shape[0]
            for mixers, _ in runs.values()
        )
    # a run's periods (layers, where the kinds do not interleave)
    return sum(
        jax.tree.leaves(positions[-1])[0].shape[0] * len(positions)
        for positions in period_stacks(stacked)
    )


def in_axis_of(key: str) -> int:
    """The contraction (input) axis of the stored weight `key`."""
    return -1 if key in OUT_MAJOR_KEYS else -2


def project(x, w, key: str):
    """x [..., in] through the dense stored weight `w` of `key`."""
    if key in OUT_MAJOR_KEYS:
        import jax.numpy as jnp

        return jnp.einsum("...d,od->...o", x, w)
    return x @ w


def lane_padded(n: int) -> int:
    """n rounded up to whole lanes."""
    return -(-n // LANES) * LANES
