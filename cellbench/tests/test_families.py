"""A family is a file found by `model_type`, and moving the two families the
benchmark has into such files changed no number (CPU, no server, seconds).

    JAX_PLATFORMS=cpu python -m pytest cellbench/tests/test_families.py -q

PINS holds values produced by the PARENT's code (commit 9113cc2, where
`checkpoint.tensor_plan`, `reference.layer_forward` and
`roofline.decode_step_needs` branched on the family), run on this sandbox's
CPU: the SHA-256 of every file `write_checkpoint` writes for the rehearsal's
two tiny presets at one seed; at each published configuration a hash of the
plan itself (file tags, tensor names, shapes and fills in order: the bits
follow from it and the seed, so no gigabyte is written here); the hashes of
`reference_logits`' `exact` and `int8` arrays on the tiny presets; every key
of `decode_step_needs` / `chunk_needs` at two (rows, context) pairs.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families  # noqa: E402
from cellbench.tests.test_rehearsal import TINY_DENSE, TINY_MOE  # noqa: E402

SEED = 2912000029
TINY = {"tiny-dense": TINY_DENSE, "tiny-moe": TINY_MOE}
PINS = {
    "files": {
        "tiny-dense": {
            "config.json": "a0981736a484832e189953e5d4c167d8bc6d1a2b0bdd4e54aea0a4609545e948",
            "model-client.safetensors": "5bee0d310d67c39187921c5762f0d7403ea862351d2218dd19cce38dc5b809b9",
            "model-layer000.safetensors": "20d87336cc08ce052512004dce8df54fb4b6d9006ba5668582a6455071155367",
            "model-layer001.safetensors": "2dbab8a19c9748f9125f2dc1d0265aea0263c29e75087a8326e3b67bf3af7a57",
            "model.safetensors.index.json": "2e1344e0e2f7f6b36ef8b031be53d4377b51bd050323bddfd421ae63180e4c04",
        },
        "tiny-moe": {
            "config.json": "485e5617b99c7cd8622d5187de12163831cd3311aac97d8ab2eeb84c05834223",
            "model-client.safetensors": "eac3ed2602f6c01321f7f5f2b464bab4ca2f0e538746134eb7f6f8cd2c06e60c",
            "model-layer000.safetensors": "17f179375d1927937a157635d6052c7ad6b05bc331ca81dba1bea7ab7b87343a",
            "model-layer001.safetensors": "b93d8fc89ed3d986d24f2abba036244e149406cdf7323d352aa6438f2e7b34c4",
            "model.safetensors.index.json": "faefc77419bc8e056d38df5231bb406fdd08862d9deaa2f46592dd57c2d339df",
        },
    },
    "plans": {
        "mistral-7b-span16": (
            "90459e0fb24f6ce5cd7d69c02ff648a360da55b085ab39ac13c9e8c7005a63fc", 17, 147),
        "qwen3-30b-a3b-span4": (
            "e576d41f202f80d527c90ea295249c1549698a78563e669b809b839a93d53650", 5, 1575),
    },
    "reference": {
        "tiny-dense": {
            "exact": "a43131701a209dbcf3a11b019c6144a27e648187fdcb3dd51c7708eb40cb396b",
            "int8": "a64aeb4cb5a5238651e4a0329df5a7221d9038ff62d9e6d018fcd64ffb509ed7"},
        "tiny-moe": {
            "exact": "7b2715b19339c8c99412fe07707723f9083414bb4b36bfdaadd6d110230f4fc9",
            "int8": "53b1005bf5a5ad1ddcbcb24d0c35e2e1c6778f05be98e0a00e3d40c4f80bc9ee"},
    },
    # (function, rows, context) -> the dict as the parent returned it; repr
    # is compared too, so an int that became a float is seen
    "needs": {
        "mistral-7b-span16": [
            ("decode_step_needs", 2.0, 3000.0,
             {"bytes": 7372701696.0, "flops": 15531507712.0,
              "weight_bytes": 6979321856, "kv_bytes": 393347072.0}),
            ("decode_step_needs", 3.5, 5000.5,
             {"bytes": 7919132672.0, "flops": 28185722880.0,
              "weight_bytes": 6979321856, "kv_bytes": 939753472.0}),
            ("chunk_needs", 128, 1536.0,
             {"bytes": 7090470912.0, "flops": 947040288768.0,
              "weight_bytes": 6979321856, "kv_bytes": 109051904.0}),
            ("chunk_needs", 128, 4608.0,
             {"bytes": 7258243072, "flops": 1032939634688.0,
              "weight_bytes": 6979321856, "kv_bytes": 276824064}),
        ],
        "qwen3-30b-a3b-span4": [
            ("decode_step_needs", 2.0, 3000.0,
             {"bytes": 787382272.0, "flops": 1303379968.0,
              "weight_bytes": 738197504.0, "kv_bytes": 49168384.0}),
            ("decode_step_needs", 3.5, 5000.5,
             {"bytes": 1273473215.9860644, "flops": 2739781632.0,
              "weight_bytes": 1130041535.9860644, "kv_bytes": 143403008.0}),
            ("chunk_needs", 128, 1536.0,
             {"bytes": 4998361732.25662, "flops": 71672266752.0,
              "weight_bytes": 4983681668.25662, "kv_bytes": 13631488.0}),
            ("chunk_needs", 128, 4608.0,
             {"bytes": 5023527556.25662, "flops": 97442070528.0,
              "weight_bytes": 4983681668.25662, "kv_bytes": 38797312.0}),
        ],
    },
}


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _published(name: str) -> dict:
    config = json.loads((ROOT / "cellbench" / "configs" / f"{name}.json").read_text())
    return {k: v for k, v in config.items() if k != "cellbench"}


@pytest.fixture(scope="module")
def tiny_ckpts(tmp_path_factory) -> dict[str, pathlib.Path]:
    root = tmp_path_factory.mktemp("ckpt")
    for name, config in TINY.items():
        checkpoint.write_checkpoint(root / name, config, SEED)
    return {name: root / name for name in TINY}


@pytest.mark.parametrize("name", sorted(TINY))
def test_checkpoint_bytes_are_the_parents(tiny_ckpts, name):
    got = {p.name: _sha(p.read_bytes())
           for p in sorted(tiny_ckpts[name].iterdir())}
    assert got == PINS["files"][name]


@pytest.mark.parametrize("name", sorted(TINY))
def test_the_two_halves_make_the_same_files(tiny_ckpts, tmp_path, name):
    """run.py writes the layer files first and the client's file later."""
    for only in ("layers", "client"):
        checkpoint.write_checkpoint(tmp_path, TINY[name], SEED, only=only)
    assert {p.name: _sha(p.read_bytes()) for p in tmp_path.iterdir()} == (
        PINS["files"][name])


@pytest.mark.parametrize("name", sorted(PINS["plans"]))
def test_plan_at_the_published_size_is_the_parents(name):
    plan = checkpoint.tensor_plan(_published(name))
    listed = [[tag, [[n, list(shape), fill] for n, shape, fill in tensors]]
              for tag, tensors in plan]
    want, files, tensors = PINS["plans"][name]
    assert (len(plan), sum(len(t) for _, t in plan)) == (files, tensors)
    assert _sha(json.dumps(listed).encode()) == want


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_logits_are_the_parents_bit_for_bit(tiny_ckpts, name):
    import jax

    from cellbench import reference

    config = TINY[name]
    ids = np.random.default_rng(SEED).integers(
        0, config["vocab_size"], (2, 40)).astype(np.int32)
    rows = [(0, 11), (0, 39), (1, 0), (1, 25)]
    with jax.default_matmul_precision("highest"):
        ref = reference.reference_logits(tiny_ckpts[name], config, ids, rows)
    assert ref["exact"].shape == (4, config["vocab_size"])
    got = {k: _sha(np.ascontiguousarray(v).tobytes()) for k, v in ref.items()}
    assert got == PINS["reference"][name]


@pytest.mark.parametrize("name", sorted(PINS["needs"]))
def test_roofline_needs_are_the_parents(name):
    config = _published(name)
    family = families.of(config)
    for function, rows, context, want in PINS["needs"][name]:
        got = getattr(family, function)(config, rows, context)
        assert got == want and repr(got) == repr(want), (function, rows, context)


def test_unknown_model_type_names_the_missing_file():
    config = dict(TINY_DENSE, model_type="falcon_h1")
    with pytest.raises(LookupError, match=r"cellbench/families/falcon_h1\.py"):
        checkpoint.tensor_plan(config)
    with pytest.raises(LookupError, match=r"cellbench/families/falcon_h1\.py"):
        families.of(config)


def test_model_type_is_read_only_where_the_family_is_looked_up():
    """Outside cellbench/families/ no code reads `model_type`, and the files
    that were the two branches hold no tensor name (docstrings apart)."""
    cb = ROOT / "cellbench"
    readers = [p.relative_to(cb).as_posix() for p in cb.rglob("*.py")
               if "model_type" in _code(p)
               and p.parent.name not in ("families", "tests")]
    assert readers == []
    for name in ("checkpoint.py", "reference.py", "roofline.py", "judge.py",
                 "metrics/step_roofline.py", "metrics/chunk_roofline.py"):
        code = _code(cb / name)
        for word in ("_proj.", ".weight", "layernorm", "embed_tokens", "lm_head",
                     "experts.", "self_attn", "qwen", "mistral"):
            assert word not in code, (name, word)


def _code(path: pathlib.Path) -> str:
    """A Python file without its docstrings and comments."""
    import ast

    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if (isinstance(body, list) and body
                and isinstance(body[0], ast.Expr)
                and isinstance(getattr(body[0], "value", None), ast.Constant)
                and isinstance(body[0].value.value, str)):
            body[0].value.value = ""
    return ast.unparse(tree)


def test_judges_cache_key_follows_the_family_file(tmp_path, monkeypatch):
    from cellbench import judge

    shutil.copytree(ROOT / "cellbench" / "families", tmp_path / "families")
    for name in ("reference.py", "checkpoint.py"):
        shutil.copy(ROOT / "cellbench" / name, tmp_path)
    monkeypatch.setattr(judge, "HERE", tmp_path)
    monkeypatch.setattr(families, "HERE", tmp_path / "families")
    plans = [{"uid": "tiny", "seed": 1, "config": c}
             for c in (TINY_DENSE, TINY_MOE)]
    keys = [judge.reference_key(p, [[1, 2]], [[0, 1]]) for p in plans]
    family = tmp_path / "families" / "mistral.py"
    family.write_text(family.read_text() + "\n# edited\n")
    again = [judge.reference_key(p, [[1, 2]], [[0, 1]]) for p in plans]
    assert keys[0] != again[0]  # the dense family's file changed
    assert keys[1] == again[1]  # the MoE family's did not


# ---- the room the hook was made for: fills that are ranges, tensors that
# ---- are neither attention nor MLP, a client whose ends are scaled
def test_range_fills_are_seeded_bfloat16_inside_their_range(tmp_path):
    import ml_dtypes

    from cellbench import reference

    a_log = {"low": 1.0, "high": 16.0, "spacing": "uniform", "then": "log"}
    dt_bias = {"low": 1e-3, "high": 1e-1, "spacing": "log",
               "then": "softplus_inverse"}
    tensors = [("mixer.in_proj.weight", (40, 8)),
               ("mixer.conv1d.weight", (12, 1, 4)),
               ("mixer.conv1d.bias", (12,), {"low": -0.1, "high": 0.1}),
               ("mixer.A_log", (3000,), a_log),
               ("mixer.D", (3000,), "ones"),
               ("mixer.dt_bias", (3000,), dt_bias),
               ("mixer.out_proj.weight", (8, 16))]
    plan = [(n, tuple(s), (f or ["bits"])[0]) for n, s, *f in tensors]
    seeds = np.random.SeedSequence(7).spawn(2)
    for path, seq in (("a", seeds[0]), ("b", seeds[0]), ("c", seeds[1])):
        checkpoint._write_file(tmp_path / path, plan, seq)
    assert (tmp_path / "a").read_bytes() == (tmp_path / "b").read_bytes()
    assert (tmp_path / "a").read_bytes() != (tmp_path / "c").read_bytes()
    got = {k: np.asarray(v, np.float64)
           for k, v in reference.read_safetensors(tmp_path / "a").items()}
    assert got["mixer.conv1d.weight"].shape == (12, 1, 4)
    decay = np.exp(got["mixer.A_log"])
    assert 0.99 <= decay.min() < 1.5 and 12 < decay.max() <= 16.1
    step = np.log1p(np.exp(got["mixer.dt_bias"]))  # softplus
    assert 0.99e-3 <= step.min() < 2e-3 and 5e-2 < step.max() <= 1.01e-1
    assert abs(np.median(np.log10(step)) + 2) < 0.1  # log-uniform: median 1e-2
    assert np.all(got["mixer.D"] == 1.0)
    assert np.abs(got["mixer.conv1d.bias"]).max() <= 0.1
    assert len(np.unique(got["mixer.conv1d.bias"])) > 6
    bits = np.abs(got["mixer.in_proj.weight"])
    assert 2.0 ** -9 <= bits.min() and bits.max() < 2.0 ** -5
    # every value is exactly a bfloat16: the file holds nothing else
    raw = reference.read_safetensors(tmp_path / "a")["mixer.dt_bias"]
    assert raw.dtype == ml_dtypes.bfloat16
