"""The delta rule's chunk form over several blocks (ops/linear_attention.py
`gdn_sequence`, `kda_sequence`): what does not read the carried state is
computed for all of a chunk's blocks in one batched pass, one forward
substitution among it, and only the state walks. Held to one rule step a
token, on the CPU at small widths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bloombee_tpu.ops.linear_attention import (
    gdn_sequence,
    gdn_step,
    kda_sequence,
    kda_step,
    l2_normalize,
    sequence_blocks,
)

CHUNK = 64
RULES = {"gdn": (gdn_step, gdn_sequence), "kda": (kda_step, kda_sequence)}
# (rtol, atol) of tests/test_qwen3_next.py and tests/test_kimi_linear.py for
# the same comparisons: the forms against the token loop, keys of one
# direction, forgetting within a token
TOL = {
    ("gdn", "random"): (1e-5, 2e-6), ("gdn", "tail"): (1e-5, 2e-6),
    ("gdn", "one_direction"): (1e-4, 1e-5),
    ("kda", "random"): (1e-4, 2e-6), ("kda", "tail"): (1e-4, 2e-6),
    ("kda", "one_direction"): (1e-4, 1e-5),
    ("kda", "forgets_in_a_token"): (1e-5, 1e-7),
}


def _inputs(rule, keys, t, h=3, k=16, v=8):
    """q, k normalised, a state that is not empty; `keys` says what the
    case bends: every key one unit vector under beta near 1 and no decay,
    the last 40 rows padding (beta = g = 0 under q, k, v that are not), or
    |g| of 16 a token a channel."""
    rng = np.random.default_rng(t + len(keys))
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, key = l2_normalize(f(t, h, k)) * k ** -0.5, l2_normalize(f(t, h, k))
    val, s0 = f(t, h, v), f(h, k, v)
    g_shape = (t, h) if rule == "gdn" else (t, h, k)
    g = -jnp.asarray(rng.uniform(0, 0.3, g_shape), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (t, h)), jnp.float32)
    if keys == "one_direction":
        key = jnp.broadcast_to(key[:1], key.shape)
        beta, g = jnp.full_like(beta, 0.999), jnp.zeros_like(g)
    elif keys == "tail":
        real = jnp.arange(t) < t - 40
        beta = jnp.where(real[:, None], beta, 0.0)
        g = jnp.where(real.reshape(t, *(1,) * (g.ndim - 1)), g, 0.0)
        val = jnp.where(real[:, None, None], val, 1e3)
    elif keys == "forgets_in_a_token":
        g = jnp.full_like(g, -16.0)
    return q, key, val, g, beta, s0


@functools.partial(jax.jit, static_argnums=0)
def _token_loop(step, rows, s0):
    def one(s, row):
        o, s = step(*(x[None] for x in row), s[None])
        return s[0], o[0]

    s, o = jax.lax.scan(one, s0, rows)
    return o, s


@pytest.mark.parametrize("blocks", [2, 8])
@pytest.mark.parametrize("rule,keys", list(TOL), ids=[f"{r}-{k}" for r, k in TOL])
def test_batched_chunk_form_equals_one_rule_step_a_token(rule, keys, blocks):
    """Outputs and final state of the batched form against the recurrence,
    over 2 and 8 blocks of 64 rows (8: a cell's 512-row chunk), and no value
    that is not finite."""
    step, sequence = RULES[rule]
    t = blocks * CHUNK
    assert sequence_blocks(t, CHUNK, rule == "kda") == blocks
    q, k, v, g, beta, s0 = _inputs(rule, keys, t)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _token_loop(step, (q, k, v, g, beta), s0)
        got_o, got_s = jax.jit(sequence, static_argnums=6)(
            q, k, v, g, beta, s0, CHUNK)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    rtol, atol = TOL[rule, keys]
    np.testing.assert_allclose(got_o, want_o, rtol=rtol, atol=atol)
    np.testing.assert_allclose(got_s, want_s, rtol=rtol, atol=atol)


@pytest.mark.parametrize("t,channel_decay,want", [
    (512, False, 8), (512, True, 8), (64, False, 1), (64, True, 1),
    (8, True, 1), (200, False, 1), (200, True, 4), (1024, False, 16)])
def test_sequence_blocks_is_what_the_forms_take(t, channel_decay, want):
    """The number `bbtpu.step` carries as `rule_blocks`: whole blocks in
    one batched pass, 1 for the single-block form (the scalar rule takes a
    ragged length as ONE block, the vector rule pads it to whole ones)."""
    assert sequence_blocks(t, CHUNK, channel_decay) == want
