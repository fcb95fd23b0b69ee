"""Phi-4-mini-flash (SambaY): Mamba-1 layers among window layers, ONE full
layer whose K/V pages the cross layers read, gated memory units on the last
Mamba layer's scan, differential attention without positions; a K/V arena
with a row a layer that WRITES keys and values, a state arena with a row a
Mamba layer; a step with three exits.

Tiny widths on the CPU (8 layers: mamba, sliding, mamba, sliding, mamba,
full, gmu, cross; hidden 128), seeded. The mathematics under test has ONE
plain copy, the benchmark's family file (cellbench/families/phi4flash.py:
the scan token by token, no cache, the layers' reads of each other through a
widened carry); everything the program serves is held to that file.
"""

import asyncio
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.checkpoint import (  # noqa: E402
    load_span_params,
    load_spec,
)
from bloombee_tpu.models.layout import split_sambay, stacked_layers  # noqa: E402
from bloombee_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from bloombee_tpu.ops.pallas.selective_scan import selective_scan  # noqa: E402
from bloombee_tpu.ops.ssm import mamba1_chunk, mamba1_step  # noqa: E402
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402

CONFIG = {
    "model_type": "phi4flash", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "sliding_window": 8, "vocab_size": 64,
    "layer_norm_eps": 1e-5, "mb_per_layer": 2, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "attention_bias": True, "max_position_embeddings": 512,
    "hidden_act": "silu", "torch_dtype": "bfloat16",
}
D, LAYERS = CONFIG["hidden_size"], CONFIG["num_hidden_layers"]
FAMILY = families.of(CONFIG)
KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_PAGED_MIN_CONTEXT": "0",
           "BBTPU_FLASH_INTERPRET": "1"}
# float32 at `highest` on both sides: what is left is the order of sums
TOL = dict(rtol=1e-5, atol=1e-6)


@pytest.fixture(autouse=True)
def _folded_as_published(monkeypatch):
    """The published ten pairs of 128 are stored folded (kv/arena.py
    `folds`); the tiny model's one pair of 64 would not be. These tests
    address the layout the cell runs: the rule says yes here."""
    from bloombee_tpu.kv import arena

    monkeypatch.setattr(arena, "folds", lambda *_: True)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_phi4flash")
    checkpoint.write_checkpoint(path, CONFIG, 45)
    return path


@pytest.fixture(scope="module")
def span(ckpt):
    return load_span_params(str(ckpt), 0, LAYERS, dtype=jnp.float32)


def _reference_hidden(ckpt, hidden, layers=LAYERS):
    """The family file's first `layers` layers over one sequence's hidden
    states [T, D], through its widened carry."""
    m = FAMILY.dims(CONFIG)
    with jax.default_matmul_precision("highest"):
        carry = jnp.concatenate([
            jnp.asarray(hidden),
            jnp.zeros((hidden.shape[0], m["inner"] + 2 * m["kv"])),
        ], -1)
        pos = jnp.arange(hidden.shape[0])
        for layer in range(layers):
            carry = FAMILY.layer_forward(
                reference.layer_params(ckpt, CONFIG, layer), CONFIG, carry,
                pos)
        return np.asarray(carry[:, :D])


def _manager(spec, layers=(0, LAYERS), pages=96, **kw):
    kw.setdefault("state_slots", 6)
    return CacheManager(
        layers[1] - layers[0], pages, 4, spec.num_key_value_heads,
        spec.head_dim, dtype=jnp.float32, ssm=spec.recurrent,
        arena_layers=spec.arena_layers(*layers), **kw)


def _executor(span, manager=None, **kw):
    params, spec = span
    return SpanExecutor(params, spec, manager or _manager(spec),
                        compute_dtype=jnp.float32, **kw)


def _hidden(seed, t, b=1):
    return (0.05 * np.random.default_rng(seed).standard_normal(
        (b, t, D))).astype(np.float32)


def _run(coro):
    with jax.default_matmul_precision("highest"):
        return asyncio.run(coro)


# ------------------------------------------------------- the scan's forms
def _scan_inputs(seed, t, c=256, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return (f(t, c), jnp.asarray(rng.uniform(1e-3, 0.2, (t, c)), jnp.float32),
            -jnp.asarray(rng.uniform(0.05, 4, (n, c)), jnp.float32),
            f(t, n), f(t, n), f(c), f(n, c))


def _token_by_token(x, dt, a_t, b, c, d, s):
    ys = []
    for t in range(x.shape[0]):
        y, s = mamba1_step(x[t:t + 1], dt[t:t + 1], a_t, b[t:t + 1],
                           c[t:t + 1], d, s[None])
        ys.append(y[0])
        s = s[0]
    return jnp.stack(ys), s


# (rows, channels, the kernel's block_c; None: `scan_block_c`'s own tile)
_SCAN_CASES = {
    "t5": (5, 256, 128), "t64": (64, 256, 128), "t200": (200, 256, 128),
    # PR 59: the tail program's one tile of 8 rows, 5 real; a pad inside the
    # last tile; four token blocks at the rule's own tile, a register a
    # state column a quarter and a half full
    "t5-rule": (5, 256, None), "t13-rule": (13, 256, None),
    "t512-c256-rule": (512, 256, None), "t512-c512-rule": (512, 512, None),
}


@pytest.mark.parametrize("case", list(_SCAN_CASES))
@pytest.mark.parametrize("form", ["chunk", "kernel"])
def test_scan_forms_equal_the_token_loop(form, case):
    """`mamba1_chunk` and the Pallas kernel (interpret mode; 200 rows cross a
    token block and end in a padded one) against one recurrence step a
    token, from a state that is not empty; the kernel's state after the last
    row against `mamba1_chunk`'s too."""
    t, c, block_c = _SCAN_CASES[case]
    args = _scan_inputs(t, t, c)
    chunk_y, chunk_s = mamba1_chunk(*args)
    # (512 rows: the chunk form, held to the token loop by the cases above)
    want_y, want_s = _token_by_token(*args) if t <= 200 else (chunk_y, chunk_s)
    if form == "chunk":
        y, s = chunk_y, chunk_s
    else:
        y, s = selective_scan(*args, block_c=block_c, interpret=True)
        np.testing.assert_allclose(s, chunk_s, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)


# dt == 0 rows of 24: a bucket's tail; rows in the MIDDLE of a tile; a whole
# tile of them between two live ones
_INERT_ROWS = {
    "tail": list(range(16, 24)), "mid-tile": [2, 3, 5, 11],
    "whole-tile": list(range(8, 16)),
}


@pytest.mark.parametrize("where", list(_INERT_ROWS))
@pytest.mark.parametrize("form", ["chunk", "kernel", "kernel-rule"])
def test_rows_with_dt_zero_are_inert(form, where):
    """dt = 0 rows (a bucket's tail, a padding row, a fused pack's rows past
    a sequence's own) neither decay nor feed the state, wherever they lie:
    the state after them is the state of the live rows alone."""
    x, dt, a_t, b, c, d, s0 = _scan_inputs(7, 24)
    dead = np.asarray(_INERT_ROWS[where])
    live = np.setdiff1d(np.arange(24), dead)
    dt = dt.at[dead].set(0.0)
    fn = mamba1_chunk if form == "chunk" else (
        lambda *a: selective_scan(
            *a, block_c=128 if form == "kernel" else None, interpret=True))
    y_padded, s_padded = fn(x, dt, a_t, b, c, d, s0)
    y_real, s_real = mamba1_chunk(
        x[live], dt[live], a_t, b[live], c[live], d, s0)
    np.testing.assert_allclose(s_padded, s_real, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_padded[live], y_real, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [0, 40, 128])
def test_flash_window_equals_masked_softmax(window):
    """The flash kernel's static window against plain masked attention."""
    rng = np.random.default_rng(window)
    t, s, h, hd = 128, 256, 2, 64
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for shape in ((1, t, h, hd), (1, s, 1, hd), (1, s, 1, hd)))
    start = 100
    got = flash_attention(q, k, v, causal=True, starts=jnp.asarray([start]),
                          lens=jnp.asarray([start + t]), window=window,
                          interpret=True)
    scores = jnp.einsum("bthd,bsgd->bhts", q, k) * hd ** -0.5
    dist = (start + jnp.arange(t))[:, None] - jnp.arange(s)[None, :]
    mask = (dist >= 0) & ((dist < window) if window else True)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    want = jnp.einsum("bhts,bsgd->bthd", probs, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------- the spec
def test_layer_kinds_and_cache_rows(ckpt):
    spec = load_spec(str(ckpt))
    assert spec.layer_types == (
        "mamba", "sliding", "mamba", "sliding", "mamba", "full", "gmu",
        "cross")
    assert spec.arena_layers(0, 8) == (3, 3)
    assert spec.arena_layers(0, 4) == (2, 2)
    # a cross layer READS the full layer's row; a gmu has none
    assert spec.cache_rows(0, 8)[5:] == (("kv", 2), ("none", -1), ("kv", 2))
    assert spec.cache_rows(2, 8)[-1] == ("kv", 1)
    assert (spec.head_dim, spec.num_key_value_heads) == (64, 1)
    assert spec.window_for_layer(1) == 8 and spec.window_for_layer(5) == 0


@pytest.mark.parametrize("start,end", [(6, 8), (4, 6), (0, 6), (5, 8),
                                       (0, 7), (1, 8), (4, 4)])
def test_a_span_that_cuts_the_cross_decoder_is_refused(ckpt, start, end):
    """Layers from the cross-decoder on read the last Mamba layer's scan and
    the full layer's pages: a span boundary inside (or an odd one) is
    refused at the spec, at the loader and at selection."""
    spec = load_spec(str(ckpt))
    assert spec.span_unsupported(start, end) is not None
    with pytest.raises(ValueError):
        load_span_params(str(ckpt), start, end, dtype=jnp.float32)


@pytest.mark.parametrize("start,end", [(0, 8), (0, 4), (2, 4), (2, 8), (4, 8)])
def test_spans_of_whole_pairs_outside_the_cut_are_served(ckpt, start, end):
    spec = load_spec(str(ckpt))
    assert spec.span_unsupported(start, end) is None
    params, _ = load_span_params(str(ckpt), start, end, dtype=jnp.float32)
    assert stacked_layers(params) == end - start
    assert set(split_sambay(params)) == {
        run for run, lo, hi in (("a", 0, 4), ("b", 4, 6), ("c", 6, 8))
        if start < hi and end > lo}


def test_selection_picks_only_servable_windows(ckpt):
    from bloombee_tpu.server.block_selection import choose_best_blocks
    from bloombee_tpu.swarm.data import ModuleInfo

    spec = load_spec(str(ckpt))
    infos = [ModuleInfo(uid=f"m.{i}", servers={}) for i in range(8)]
    assert choose_best_blocks(infos, {}, 6, spec=spec) == (2, 8)
    assert choose_best_blocks(infos, {}, 2, spec=spec) == (0, 2)
    with pytest.raises(ValueError, match="may not cut|whole"):
        choose_best_blocks(infos, {}, 3, spec=spec)


# ---------------------------------------------------- the program, served
@pytest.mark.parametrize("layers", [4, 8])
def test_whole_prompt_equals_the_reference(ckpt, layers):
    """Every kind of layer against the family file: the (mamba, window)
    pairs alone (a span that ends before the cut returns every row), then
    the full stack."""
    params, spec = load_span_params(str(ckpt), 0, layers, dtype=jnp.float32)
    manager = _manager(spec, (0, layers))
    ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32)
    hidden = _hidden(1, 29)

    async def go():
        async with manager.allocate(1, 64) as h:
            return ex.prefill(h, hidden)

    np.testing.assert_allclose(
        _run(go())[0], _reference_hidden(ckpt, hidden[0], layers), **TOL)


@pytest.mark.parametrize("cuts", [(13, 29, 37), (5, 6, 22, 37), (37,)])
def test_uneven_chunks_then_decode_equal_the_reference_logits(
        ckpt, span, cuts):
    """Prefill in uneven chunks (bucket tails of dt = 0 rows between them),
    then three decode steps, through both arenas: the LOGITS of every row
    against the family file's full forward."""
    ex = _executor(span)
    hidden = _hidden(2, 40)
    client = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.CLIENT_SHARD))

    async def go():
        outs, s = [], 0
        async with ex.manager.allocate(1, 64) as h:
            for e in cuts:
                outs.append(ex.prefill_chunk(
                    h, hidden[:, s:e], commit=True, fetch=True))
                s = e
            for t in range(37, 40):
                outs.append(ex.decode(h, hidden[:, t:t + 1]))
        return np.concatenate(outs, 1)[0]

    got, want = _run(go()), _reference_hidden(ckpt, hidden[0])
    np.testing.assert_allclose(got, want, **TOL)
    pad = lambda h: np.concatenate(  # noqa: E731
        [h, np.zeros((h.shape[0], 1))], -1)[:, :D]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            FAMILY.logits_rows(client, CONFIG, pad(got)),
            FAMILY.logits_rows(client, CONFIG, pad(want)),
            rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("tail", [1, 3, 20])
def test_short_program_and_long_tail_equal_all_layers_at_the_reply_rows(
        span, tail):
    """Chunks that carry no reply row end after the shared layers and come
    back as zeros; the chunk that answers runs the cross-decoder on its
    reply rows alone (1 and 3: gathered; 20 > the block: every row, the
    others zeroed). At the reply rows both equal all layers over all rows:
    the same sums row by row (float32 on the CPU: within two units in the
    last place, the matmuls' row blocks differ)."""
    hidden = _hidden(3, 45)

    async def go(reply):
        ex = _executor(span)
        outs, s = [], 0
        async with ex.manager.allocate(1, 64) as h:
            for e in (16, 32, 45):
                n = None if reply is None else max(
                    0, e - max(s, 45 - reply))
                outs.append(ex.prefill_chunk(
                    h, hidden[:, s:e], commit=True, fetch=True,
                    reply_rows=n))
                s = e
        return np.concatenate(outs, 1)[0], dict(ex.sambay)

    every, counts_all = _run(go(None))
    got, counts = _run(go(tail))
    np.testing.assert_allclose(got[-tail:], every[-tail:], rtol=0, atol=1e-7)
    assert not got[:-tail].any()
    assert counts_all["cross_rows"] == counts_all["self_rows"] == 45
    assert counts["cross_rows"] == tail and counts["self_rows"] == 45
    short = sum(1 for s, e in ((0, 16), (16, 32), (32, 45))
                if e <= 45 - tail)
    assert (counts["short_steps"], counts["long_steps"]) == (short, 3 - short)
    assert counts["shared_kv_reads"] == 3 - short  # one cross layer


def test_padding_rows_of_a_decode_group_are_inert(ckpt, span):
    """Three sessions decode in a four-row bucket: each equals its own
    reference, and the padding row writes no page and no state."""
    ex = _executor(span)
    hiddens = [_hidden(10 + i, 9 + i) for i in range(3)]

    async def go():
        handles = []
        for hid in hiddens:
            h = await ex.manager.allocate(1, 32).__aenter__()
            ex.prefill(h, hid[:, :-1])
            handles.append(h)
        out, combined = ex.decode_group(
            handles, [hid[:, -1:] for hid in hiddens])
        ex.manager.commit(combined)
        return np.asarray(out)[:, 0]

    got = _run(go())
    for i, hid in enumerate(hiddens):
        np.testing.assert_allclose(
            got[i], _reference_hidden(ckpt, hid[0])[-1], **TOL)


@pytest.mark.parametrize("reply", [None, 0, 1])
def test_a_fused_pack_equals_its_members_alone(ckpt, span, reply):
    """One prefill chunk beside two decode rows in ONE ragged dispatch: the
    decode rows and the chunk's reply rows equal the reference; rows the
    caller does not read are zeros."""
    ex = _executor(span)
    dec = [_hidden(20, 12), _hidden(21, 7)]
    chunk = _hidden(22, 21)

    async def go():
        handles = []
        for hid in dec:
            h = await ex.manager.allocate(1, 32).__aenter__()
            ex.prefill(h, hid[:, :-1])
            handles.append(h)
        hc = await ex.manager.allocate(1, 32).__aenter__()
        ex.prefill_chunk(hc, chunk[:, :8], commit=True, reply_rows=0)
        out, _ = ex.ragged_group(
            handles + [hc], [d[:, -1:] for d in dec] + [chunk[:, 8:]],
            reply_rows=[None, None, reply])
        return np.asarray(out)

    got = _run(go())
    for i, hid in enumerate(dec):
        np.testing.assert_allclose(
            got[i], _reference_hidden(ckpt, hid[0])[-1], **TOL)
    want = _reference_hidden(ckpt, chunk[0])[8:]
    n = 13 if reply is None else reply
    if n:
        np.testing.assert_allclose(got[2:][-n:], want[-n:], **TOL)
    assert not got[2: 2 + 13 - n].any()


def test_cross_layers_read_the_shared_row_across_pages_and_reuse(ckpt, span):
    """The cross layers' page table is the full layer's: after decode steps
    append a page, and after a session is closed and another takes its
    pages and its state slot, every row still equals the reference."""
    params, spec = span
    manager = _manager(spec, pages=12, state_slots=1)
    ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32)

    async def one(seed, t):
        hidden = _hidden(seed, t)
        async with manager.allocate(1, 24) as h:
            outs = [ex.prefill(h, hidden[:, :3])]
            for i in range(3, t):  # pages of 4: a page is appended thrice
                outs.append(ex.decode(h, hidden[:, i:i + 1]))
        np.testing.assert_allclose(
            np.concatenate(outs, 1)[0], _reference_hidden(ckpt, hidden[0]),
            **TOL)

    _run(one(30, 14))
    _run(one(31, 17))  # the freed pages and the one state slot, reused


def test_kernel_paths_equal_the_reference(ckpt, span, monkeypatch):
    """The same steps with the Pallas kernels on (interpret mode): the
    selective-scan kernel, paged decode attention (window and full, and the
    cross layers' gathered rows), the windowed flash form under its tile
    falls back to dense scores."""
    for k, v in KERNELS.items():
        monkeypatch.setenv(k, v)
    ex = _executor(span)
    hidden = _hidden(4, 27)

    async def go():
        async with ex.manager.allocate(1, 64) as h:
            outs = [ex.prefill_chunk(h, hidden[:, :16], commit=True,
                                     fetch=True),
                    ex.prefill_chunk(h, hidden[:, 16:24], commit=True,
                                     fetch=True, reply_rows=2)]
            for t in range(24, 27):
                outs.append(ex.decode(h, hidden[:, t:t + 1]))
        return np.concatenate(outs, 1)[0]

    got, want = _run(go()), _reference_hidden(ckpt, hidden[0])
    keep = np.r_[0:16, 22:27]
    np.testing.assert_allclose(got[keep], want[keep], rtol=1e-3, atol=1e-4)
    assert ex.attn_dispatches["paged"] == 5 and ex.kernel_fallbacks == 0
    # 16- and 8-row chunks are under the flash kernel's block: the layers
    # took their dense tail, and the executor says no tile
    assert ex.flash_form is None


@pytest.mark.parametrize("kv_pairs,pages", [(10, 4), (4, 1)])
def test_a_decode_step_streams_pages_by_the_rows_of_a_page(
        tmp_path, monkeypatch, step_spans, kv_pairs, pages):
    """One served decode step with the kernels on (interpret) at the
    published page: 16 tokens of 10 K/V pairs, 160 rows, go 4 a grid step
    (`_pages_per_step`) in the window layers (a 24-token window at 101
    tokens of an 8-page bucket: the first step lies below it whole), the
    full layer and the cross layer (the context ends inside the second
    step); equal to the same step without kernels on the same caches, and
    `bbtpu.step` says `decode_pages` 4. A span of 4 pairs has 64-row pages:
    one a step. The executor counts the turns the step's four calls walked
    (`kv_walk`: two window layers, the full and the cross layer), every one
    of them live: 1 + 1 + 2 + 2 groups of 4 pages where the bucket's walk
    took 2 a call, 3 + 3 + 7 + 7 pages where it took 8."""
    config = {**CONFIG, "hidden_size": 32 * kv_pairs, "sliding_window": 24,
              "num_attention_heads": 2 * kv_pairs,
              "num_key_value_heads": 2 * kv_pairs}
    checkpoint.write_checkpoint(tmp_path, config, 53)
    params, spec = load_span_params(
        str(tmp_path), 0, LAYERS, dtype=jnp.float32)
    assert spec.num_key_value_heads == kv_pairs
    hidden = (0.05 * np.random.default_rng(53).standard_normal(
        (1, 101, config["hidden_size"]))).astype(np.float32)

    def step(switches):
        manager = CacheManager(
            LAYERS, 12, 16, kv_pairs, spec.head_dim, dtype=jnp.float32,
            ssm=spec.recurrent, arena_layers=spec.arena_layers(0, LAYERS),
            state_slots=2)
        ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32)

        async def go():
            async with manager.allocate(1, 128) as h:
                ex.prefill_chunk(h, hidden[:, :100], commit=True, fetch=True)
                with monkeypatch.context() as m:
                    for k, v in switches.items():
                        m.setenv(k, v)
                    return ex.decode(h, hidden[:, 100:])

        return _run(go())[0], ex

    want, plain = step({})
    got, ex = step(KERNELS)
    np.testing.assert_allclose(got, want, **TOL)
    assert plain.attn_dispatches["paged"] == 0
    assert ex.attn_dispatches["paged"] == 1 and ex.kernel_fallbacks == 0
    decodes = [s for s in step_spans if s["kind"] == "decode"]
    assert [s.get("decode_pages") for s in decodes] == [None, pages]
    turns = 6 if pages == 4 else 20
    assert ex.kv_walk == {"turns": turns, "live_turns": turns}
    assert plain.kv_walk == {"turns": 0, "live_turns": 0}
    assert all("decode_pages" not in s for s in step_spans
               if s["kind"] == "chunk")


@pytest.mark.parametrize("layers,kinds", [
    ((0, 8), ("window", "full")), ((0, 4), ("window",)), ((4, 8), ("full",)),
])
def test_the_flash_form_names_the_attention_layers_a_span_holds(
        ckpt, layers, kinds):
    """`_flash_form` goes by the layers that attend: a span of (mamba,
    window) pairs alone has no full layer to name, the cross-decoder's
    span (the full layer and the cross layers that read it) no window; each
    kind's tile is the rule's at the run `_diff_attend` gathers, and a
    chunk under the kernel's block has none."""
    from bloombee_tpu.ops.pallas.flash_attention import flash_tiles
    from bloombee_tpu.runtime.layer_body import chunk_run_pages

    params, spec = load_span_params(str(ckpt), *layers, dtype=jnp.float32)
    manager = _manager(spec, layers, pages=512)
    ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32,
                      start_block=layers[0])
    want = {}
    for kind in kinds:
        window = spec.sliding_window if kind == "window" else 0
        keys = 4 * chunk_run_pages(128, window, 4, 256)
        want[kind] = "x".join(map(str, flash_tiles(
            128, keys, spec.gqa_groups, spec.head_dim, 4)))
    assert ex._flash_form(128, 256) == "+".join(
        f"{k}:{v}" for k, v in want.items())
    assert ex.flash_form == ex._flash_form(128, 256)
    assert ex._flash_form(16, 256) is None
    assert ex.flash_form is not None  # the last tile a chunk had stays


def test_paths_that_cut_or_copy_a_cache_are_refused(ckpt, span):
    params, spec = span
    with pytest.raises(ValueError, match="state_slots"):
        CacheManager(LAYERS, 16, 4, 1, 64, dtype=jnp.float32,
                     ssm=spec.recurrent,
                     arena_layers=spec.arena_layers(0, LAYERS))
    manager = _manager(spec, prefix_cache=True)
    assert manager.prefix_cache is False
    assert manager.state_refusals == {"prefix cache": 1}
    with pytest.raises(ValueError, match="arena_layers"):
        SpanExecutor(params, spec, CacheManager(
            LAYERS, 16, 4, 1, 64, dtype=jnp.float32, ssm=spec.recurrent,
            state_slots=2), compute_dtype=jnp.float32)
    ex = _executor(span)

    async def go():
        async with ex.manager.allocate(1, 32) as h:
            with pytest.raises(ValueError, match="whole span"):
                ex.prefill(h, _hidden(5, 4), layers=(2, 8))
            with pytest.raises(ValueError, match="tree verify"):
                ex.decode(h, _hidden(5, 4), tree_mask=np.ones((1, 4, 4), bool))

    _run(go())
    from bloombee_tpu.runtime.layer_body import dense_unsupported

    assert dense_unsupported(spec) is not None
