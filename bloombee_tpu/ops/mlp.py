"""Gated MLP (SiLU / SwiGLU).

Replaces /root/reference/src/bloombee/flexgen_utils/pytorch_backend.py:1033
`mlp_llama`. XLA fuses the elementwise silu/mul into the surrounding matmuls.
"""

from __future__ import annotations

import jax


def silu_mlp(
    x: jax.Array,
    gate_w: jax.Array,  # [D, I]
    up_w: jax.Array,  # [D, I]
    down_w: jax.Array,  # [I, D]
    gate_mult: float | None = None,  # on the gate, before the activation
    out_mult: float | None = None,  # on the output, after the down projection
) -> jax.Array:
    g = x @ gate_w
    if gate_mult is not None:
        g = g * gate_mult
    u = x @ up_w
    y = (jax.nn.silu(g) * u) @ down_w
    return y if out_mult is None else y * out_mult
