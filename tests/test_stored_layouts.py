"""Stored layouts of stacked weights (bloombee_tpu/models/layout.py), on the
CPU at tiny widths: q/k/v projections lie output-major, as the checkpoint has
them, and Falcon-H1's `in_proj` carries zero columns up to whole lanes.

What is held here: a checkpoint loaded through the REAL loader still gives
the plain float32 reference's logits (the benchmark's family files, which
read the checkpoint's own names and know nothing of the stored layout); the
padding columns are never read; `--tp` shards q/k/v on the output axis;
weight quantisation is per OUTPUT channel whichever axis that is.
"""

import asyncio
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.models.layout import (  # noqa: E402
    OUT_MAJOR_KEYS,
    lane_padded,
)
from bloombee_tpu.models.wquant import (  # noqa: E402
    dequantize_weight,
    quantize_span_params,
    quantize_weight,
)
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402
from cellbench.tests.test_rehearsal import TINY_DENSE, TINY_MOE  # noqa: E402
from tests.test_falcon_h1 import CONFIG as TINY_FALCON_H1  # noqa: E402

TINY = {
    "llama": TINY_DENSE,  # model_type mistral: the llama block's loader
    "qwen3_moe": TINY_MOE,
    "falcon_h1": TINY_FALCON_H1,
}
SEED = 3400000017


@pytest.fixture(scope="module")
def ckpts(tmp_path_factory):
    root = tmp_path_factory.mktemp("stored_layouts")
    for name, config in TINY.items():
        checkpoint.write_checkpoint(root / name, config, SEED)
    return root


def _span(ckpts, name):
    layers = TINY[name]["num_hidden_layers"]
    return load_span_params(str(ckpts / name), 0, layers, dtype=jnp.float32)


def _executor(params, spec, **kw):
    manager = CacheManager(
        spec.num_hidden_layers, 64, 16, spec.num_key_value_heads,
        spec.head_dim, dtype=jnp.float32, ssm=spec.ssm,
        **({"state_slots": 8} if spec.ssm is not None else {}))
    return SpanExecutor(params, spec, manager, compute_dtype=jnp.float32, **kw)


def _serve(ex, hidden, chunk=16, decode=4):
    """[1, T, D] through chunked prefill (a tail in a wider bucket) and the
    last `decode` positions as single steps; -> [T, D]."""

    async def run():
        t = hidden.shape[1]
        async with ex.manager.allocate(1, 128) as handle:
            outs = [ex.prefill_chunked(handle, hidden[:, :t - decode], chunk)]
            for i in range(t - decode, t):
                outs.append(ex.decode(handle, hidden[:, i:i + 1]))
            return np.concatenate(outs, 1)[0]

    return asyncio.run(run())


@pytest.mark.parametrize("name", sorted(TINY))
def test_loaded_span_gives_the_references_logits(ckpts, name):
    config = TINY[name]
    family = families.of(config)
    params, spec = _span(ckpts, name)
    d, heads, kv = spec.hidden_size, spec.num_attention_heads, \
        spec.num_key_value_heads
    for key, out in (("q_proj", heads), ("k_proj", kv), ("v_proj", kv)):
        assert params[key].shape[1:] == (out * spec.head_dim, d), key
    ids = np.random.default_rng(1).integers(0, config["vocab_size"], (1, 41))
    rows = [(0, t) for t in (0, 15, 31, 36, 37, 40)]
    client = reference.read_safetensors(
        ckpts / name / checkpoint.file_name(reference.CLIENT_SHARD))
    with jax.default_matmul_precision("highest"):
        want = reference.reference_logits(
            ckpts / name, config, ids, rows)["exact"]
        hidden = np.asarray(family.embed(client, config, ids), np.float32)
        out = _serve(_executor(params, spec), hidden)
        got = np.asarray(family.logits_rows(
            client, config, jnp.stack([out[t] for _, t in rows])))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * scale)


# ------------------------------------------------- in_proj's padding columns
def _planted(params, spec):
    """The span with nonzero values where in_proj's padding columns are."""
    w = params["ssm_in_proj"]
    assert w.shape[-1] == lane_padded(spec.ssm.proj_dim) > spec.ssm.proj_dim
    assert not np.asarray(w[..., spec.ssm.proj_dim:]).any()  # stored zeros
    junk = np.random.default_rng(2).standard_normal(
        (*w.shape[:-1], w.shape[-1] - spec.ssm.proj_dim)).astype(np.float32)
    return dict(params, ssm_in_proj=w.at[..., spec.ssm.proj_dim:].set(
        1e3 * junk))


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_in_proj_padding_columns_are_never_read(ckpts, program):
    """Values PLANTED in the padding columns change no bit of a decode
    step's or a chunk's output, nor of the state they leave."""
    params, spec = _span(ckpts, "falcon_h1")
    hidden = np.random.default_rng(3).standard_normal(
        (1, 24, spec.hidden_size)).astype(np.float32)

    def run(p):
        ex = _executor(p, spec)

        async def go():
            async with ex.manager.allocate(1, 64) as handle:
                out = ex.prefill_chunk(handle, hidden[:, :16])
                if program == "step":
                    out = ex.decode(handle, hidden[:, 16:17], commit=False)
                slot = int(ex.manager.state_slots(handle)[0])
                return (np.asarray(out),
                        np.asarray(ex.manager.state["ssm"][:, slot]),
                        np.asarray(ex.manager.state["conv"][:, slot]))

        return asyncio.run(go())

    for a, b in zip(run(params), run(_planted(params, spec))):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------- --tp
def test_tp_shards_qkv_on_the_output_axis_and_matches_unsharded(ckpts):
    from bloombee_tpu.parallel.serving import (
        make_serving_mesh,
        place_span_params,
    )

    params, spec = _span(ckpts, "llama")
    mesh = make_serving_mesh(2)
    placed = place_span_params(params, mesh)
    # the span's own output-major keys (the constant also names latent
    # attention's, whose family refuses --tp)
    keys = sorted(OUT_MAJOR_KEYS & params.keys())
    assert keys == ["k_proj", "q_proj", "v_proj"]
    for key in keys:
        layers, out, d = params[key].shape
        assert placed[key].sharding.spec == P(None, "tp", None), key
        assert {s.data.shape for s in placed[key].addressable_shards} == {
            (layers, out // 2, d)}, key
    hidden = np.random.default_rng(4).standard_normal(
        (1, 21, spec.hidden_size)).astype(np.float32)
    want = _serve(_executor(params, spec), hidden)
    got = _serve(_executor(params, spec, mesh=mesh), hidden)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ --weight-quant
@pytest.mark.parametrize("bits", [8, 4])
def test_an_output_major_key_quantises_per_output_channel(bits):
    """`q_proj` stored [L, out, in] gets the codes and scales that the same
    weights stored [L, in, out] got before the layout changed, transposed;
    a key that stayed [L, in, out] is quantised as it was."""
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((2, 96, 64)), jnp.float32)  # out, in
    stacked = {"q_proj": w, "o_proj": w}
    got = quantize_span_params(stacked, bits)
    before = quantize_weight(jnp.swapaxes(w, -1, -2), bits)  # [L, in, out]
    for leaf, ref in zip(got["q_proj"], before):
        if ref is not None:
            assert np.array_equal(
                np.asarray(leaf), np.asarray(jnp.swapaxes(ref, -1, -2)))
    if bits == 8:
        assert got["q_proj"].scale.shape == (2, 96, 1)  # one a channel
    back = dequantize_weight(got["q_proj"], jnp.float32, in_axis=-1)
    was = jnp.swapaxes(dequantize_weight(before, jnp.float32), -1, -2)
    assert np.array_equal(np.asarray(back), np.asarray(was))
    for leaf, ref in zip(got["o_proj"], quantize_weight(w, bits)):
        if ref is not None:
            assert np.array_equal(np.asarray(leaf), np.asarray(ref))


def test_int8_span_through_the_loader_stays_close_to_dense(ckpts):
    """The loaded Falcon-H1 span under `--weight-quant int8`: q/k/v by
    output channel, in_proj's padding columns zero codes, the served output
    close to the dense span's."""
    params, spec = _span(ckpts, "falcon_h1")
    q = quantize_span_params(params, 8)
    assert q["q_proj"].scale.shape[-1] == 1
    assert q["o_proj"].scale.shape[-2] == 1
    assert not np.asarray(
        q["ssm_in_proj"].codes[..., spec.ssm.proj_dim:]).any()
    hidden = np.random.default_rng(6).standard_normal(
        (1, 21, spec.hidden_size)).astype(np.float32)
    dense = _serve(_executor(params, spec), hidden)
    quant = _serve(_executor(q, spec), hidden)
    cos = np.vdot(dense, quant) / (
        np.linalg.norm(dense) * np.linalg.norm(quant))
    assert cos > 0.995, cos
