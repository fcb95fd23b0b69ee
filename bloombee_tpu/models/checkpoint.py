"""HF checkpoint reading (safetensors, torch-free).

Replaces the reference's per-block HF-hub state-dict loading and .npy weight
conversion (/root/reference/src/bloombee/server/from_pretrained.py:58-548,
models/llama/block.py:329-384): server loads only its span's layers; client
loads only embeddings + final norm + lm head (reference
client/from_pretrained.py:17-70 skips `model.layers.*`).

Zero-egress note: model directories are local paths (config.json +
*.safetensors [+ index]); hub download plumbing can wrap this later.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np
from safetensors import safe_open

from bloombee_tpu.models.layout import OUT_MAJOR_KEYS
from bloombee_tpu.models.spec import ModelSpec


class CheckpointReader:
    """Lazy tensor reader over a local HF model directory."""

    def __init__(self, model_dir: str | pathlib.Path, experts=None):
        self.dir = pathlib.Path(model_dir)
        # (first, count) of the published expert numbering a server was told
        # to hold (`run_server --experts`); None = all the checkpoint has
        self.experts = experts
        with open(self.dir / "config.json") as f:
            self.config = json.load(f)
        index_path = self.dir / "model.safetensors.index.json"
        if index_path.exists():
            with open(index_path) as f:
                index = json.load(f)
            self._weight_map = index["weight_map"]
        else:
            files = sorted(self.dir.glob("*.safetensors"))
            if not files:
                raise FileNotFoundError(f"no safetensors in {self.dir}")
            self._weight_map = {}
            for fp in files:
                with safe_open(fp, framework="numpy") as f:
                    for k in f.keys():
                        self._weight_map[k] = fp.name
        self._handles: dict[str, object] = {}

    def keys(self):
        return self._weight_map.keys()

    def has(self, name: str) -> bool:
        return name in self._weight_map

    def tensor(self, name: str) -> np.ndarray:
        fname = self._weight_map[name]
        h = self._handles.get(fname)
        if h is None:
            h = safe_open(self.dir / fname, framework="numpy")
            self._handles[fname] = h
        return h.get_tensor(name)

    def model_type(self) -> str:
        return self.config.get("model_type", "llama")


def read_tensor(reader: CheckpointReader, name: str, dtype=None):
    """Read one tensor as a jnp array with optional dtype cast (the shared
    helper for family weight converters)."""
    import jax.numpy as jnp

    w = jnp.asarray(reader.tensor(name))
    return w.astype(dtype) if dtype is not None else w


def read_weight(reader: CheckpointReader, name: str, key: str, dtype=None):
    """The torch `[out, in]` matrix `name` as param `key` is STORED
    (models/layout.py): as the checkpoint has it for an output-major key,
    transposed to `[in, out]` for every other projection."""
    w = read_tensor(reader, name, dtype)
    return w if key in OUT_MAJOR_KEYS else w.T


def stack_expert_weights(
    reader, expert_fmt: str, gate_name: str, up_name: str, down_name: str,
    n_experts: int, dtype=None, first: int = 0,
) -> dict:
    """Stack per-expert gate/up/down matrices into [E, D, I] / [E, I, D]
    tensors (the dense-over-experts MoE layout shared by Mixtral and
    Qwen3-MoE loaders). expert_fmt receives the expert index; `first` is
    the first expert read (a server that holds a share of them)."""
    import jax
    import jax.numpy as jnp

    def stacked(name: str):
        # one projection's stack at a time, SETTLED before the next is
        # read: dispatch is asynchronous and a buffer is allocated when its
        # op is enqueued, so three lists and three stacks enqueued at once
        # held a layer's experts three times over (5.4 GB at 32 experts of
        # 57 MB, and the load's peak stood at 15.6 of the chip's 16.9 GB)
        return jax.block_until_ready(jnp.stack([
            read_tensor(
                reader, f"{expert_fmt.format(e)}.{name}.weight", dtype
            ).T
            for e in range(first, first + n_experts)
        ]))

    return {
        "experts_gate": stacked(gate_name),
        "experts_up": stacked(up_name),
        "experts_down": stacked(down_name),
    }


def split_query_rows(q, heads: int, nope: int, rope: int, dtype=None,
                     perm=None) -> dict:
    """The query's up-projection (`q_b_proj` [heads * (nope + rope),
    q_rank], or a full-rank `q_proj` [.., D]) as the rows that make q_nope
    and the rows that make q_pe, each a projection of its own (cut out of
    the fused product, every layer re-laid its weight out first); `perm`
    de-interleaves the rotary rows."""
    import jax.numpy as jnp
    import numpy as np

    q = np.asarray(q).reshape(heads, nope + rope, -1)
    q_rope = q[:, nope:] if perm is None else q[:, nope:][:, perm]
    return {
        "q_b_nope": jnp.asarray(
            q[:, :nope].reshape(heads * nope, -1), dtype=dtype),
        "q_b_rope": jnp.asarray(q_rope.reshape(heads * rope, -1), dtype=dtype),
    }


def load_spec(model_dir: str, experts=None) -> ModelSpec:
    """ModelSpec from a local model dir via the family registry."""
    from bloombee_tpu.models.auto import get_family

    reader = CheckpointReader(model_dir, experts)
    return get_family(reader.model_type()).spec_from_reader(reader)


def _stack_settled(per_layer: list):
    """`stack_params`, finished before it returns: dispatch is asynchronous
    and a buffer is allocated when its op is enqueued, so a stack that is
    only enqueued holds its inputs AND its output, and a span's leaves
    enqueued one after the other held the span twice."""
    import jax

    from bloombee_tpu.utils.tree import stack_params

    return jax.block_until_ready(stack_params(per_layer))


def _stack_runs(layers: list[dict], period_runs: tuple = (),
                runs: list[str] | None = None, units: bool = False) -> dict:
    """Per-layer params -> the stacked dict, leaf by leaf, letting go of
    each layer's tensor once it is stacked (the span is never held twice).
    Layers of one kind (the same keys) are ONE stack; a span whose first
    layers have other keys than the rest (a dense MLP before sparse ones)
    is two, the leading run under models/layout.py `LEAD`. Each leaf's stack
    is settled before the next leaf's layers are let go (`_stack_settled`).
    `period_runs` (`ModelSpec.period_runs`): the kinds interleave, the last
    layer of every period of another kind than the ones before it: one stack
    a POSITION in the period, each [periods, ...], the j-th leading layers'
    under `linear_prefix(j)`, the closing layers' under the plain keys; of
    two runs of like periods (the model's leading dense layer inside the
    first period, or a short last period) the first's stacks under `LEAD`
    besides. `units`: `period_runs` is a LIST of runs of a repeated unit of
    kinds (`ModelSpec.one_sublayer`). `runs`: each layer's run of a SambaY
    span ("a" | "b" | "c")."""
    from bloombee_tpu.models.layout import LEAD, linear_prefix

    stack_params = _stack_settled

    if runs:
        # a SambaY span: one stack a (run, position) of its (mixer,
        # attention) pairs (models/layout.py `sambay_prefix`)
        from bloombee_tpu.models.layout import sambay_prefix

        out = {}
        for run in dict.fromkeys(runs):
            mine = [p for p, r in zip(layers, runs) if r == run]
            for j in (0, 1):
                stack = mine[j::2]
                for key in list(stack[0]):
                    out[sambay_prefix(run, j) + key] = stack_params(
                        [p.pop(key) for p in stack]
                    )
        return out
    if period_runs:
        # `units`: one stack a (run, position in the run's unit), however
        # many runs (models/layout.py `unit_prefix`)
        from bloombee_tpu.models.layout import unit_prefix

        out, at = {}, 0
        for r, (kinds, periods) in enumerate(period_runs):
            per = len(kinds)
            lead = LEAD if r == 0 and len(period_runs) > 1 else ""
            mine = layers[at : at + periods * per]
            at += periods * per
            for j in range(per):
                prefix = unit_prefix(r, j) if units else lead + (
                    linear_prefix(j) if j < per - 1 else "")
                run = mine[j::per]
                for key in list(run[0]):
                    out[prefix + key] = stack_params(
                        [p.pop(key) for p in run]
                    )
        return out
    kinds = [frozenset(p) for p in layers]
    cut = next((i for i, k in enumerate(kinds) if k != kinds[0]), len(layers))
    if any(k != kinds[-1] for k in kinds[cut:]):
        raise NotImplementedError(
            "a span of more than two runs of same-kind layers"
        )
    out = {}
    for prefix, run in (
        ((LEAD, layers[:cut]), ("", layers[cut:])) if cut < len(layers)
        else (("", layers),)
    ):
        for key in list(run[0]):
            out[prefix + key] = stack_params([p.pop(key) for p in run])
    return out


def load_span_params(
    model_dir: str, start: int, end: int, dtype=None,
    adapter_dirs: list[str] | None = None, experts=None,
):
    """Stacked per-layer params for blocks [start, end), with optional LoRA
    adapters merged into the base weights (W' = W + alpha/r * B A — the
    capability of the reference's utils/peft.py LoraLinear; merging at load
    keeps the serving path a plain matmul)."""
    from bloombee_tpu.models.auto import get_family

    reader = CheckpointReader(model_dir, experts)
    family = get_family(reader.model_type())
    spec = family.spec_from_reader(reader)
    if experts is not None and not spec.num_experts:
        raise ValueError(f"--experts given, but {spec.family} has no experts")
    reason = spec.span_unsupported(start, end)
    if reason is not None:
        raise ValueError(reason)
    if spec.mamba is not None and adapter_dirs:
        raise ValueError(
            f"LoRA adapters unsupported for {spec.family}: q/k/v are cut out "
            "of one fused projection and the mixers have none an adapter names"
        )
    if spec.one_sublayer and adapter_dirs:
        raise ValueError(
            f"LoRA adapters unsupported for {spec.family}: its mixer and "
            "expert layers have no projection an adapter names"
        )
    if spec.gdn is not None and adapter_dirs:
        raise ValueError(
            f"LoRA adapters unsupported for {spec.family}: the full layers' "
            "q_proj is stored split (query and gate rows, or a latent "
            "query's nope and rope rows), and the linear layers have no "
            "projection an adapter names"
        )
    adapters = [LoraAdapter(d) for d in (adapter_dirs or [])]
    layers = []
    for i in range(start, end):
        params = family.load_block_params(reader, i, dtype=dtype)
        for adapter in adapters:
            params = adapter.merge_into(params, i)
        layers.append(params)
    if spec.heterogeneous:
        # per-layer shapes differ (gemma-4): no stacking — the hetero span
        # step unrolls over a tuple of per-layer param dicts
        return tuple(layers), spec
    if spec.mamba is not None:
        return _stack_runs(layers, runs=sambay_runs(spec, start, end)), spec
    return _stack_runs(
        layers,
        spec.period_runs(start, end) if spec.kinds_interleave else (),
        units=spec.one_sublayer,
    ), spec


def sambay_runs(spec: ModelSpec, start: int, end: int) -> list[str]:
    """Each layer's run of a SambaY span [start, end): "a" up to the last
    mamba layer, "b" that layer and the full one, "c" the cross-decoder."""
    shared = spec.cross_start - 2
    return [
        "a" if i < shared else "b" if i < spec.cross_start else "c"
        for i in range(start, end)
    ]


def held_experts(reader, config_key: str) -> tuple[int, int]:
    """[first, count) of the published expert numbering this server loads:
    what it was told (`--experts`), else every expert of the checkpoint
    (`config_key`: the config's name for their number)."""
    held = getattr(reader, "experts", None)
    return tuple(held) if held else (0, reader.config[config_key])


def refine_held(spec: ModelSpec, reader, config_key: str,
                router_name: str = "mlp.gate.weight",
                layer_prefix: str = "model.layers") -> ModelSpec:
    """What the config alone does not say of a family whose experts are
    shared among chips: the router's width (a checkpoint cut to one chip's
    share of the experts keeps the router over ALL of them, so it is read
    off the router's tensor, `router_name` under a layer) and the experts
    held."""
    import dataclasses

    if not spec.num_experts:
        return spec
    first_sparse = next(
        (i for i in range(spec.num_hidden_layers)
         if reader.has(f"{layer_prefix}.{i}.{router_name}")), None,
    )
    width = spec.num_experts
    if first_sparse is not None:
        width = reader.tensor(
            f"{layer_prefix}.{first_sparse}.{router_name}"
        ).shape[0]
    first, count = held_experts(reader, config_key)
    if first < 0 or count < 1 or first + count > width:
        raise ValueError(
            f"--experts {first}:{count} outside the router's {width} experts"
        )
    if spec.moe_groups and width % spec.moe_groups:
        raise ValueError(
            f"router width {width} not divisible into {spec.moe_groups} groups"
        )
    return dataclasses.replace(
        spec, num_experts=width,
        moe_held=None if (first, count) == (0, width) else (first, count),
    )


def load_span_params_split(
    model_dir: str, start: int, end: int, resident: int, dtype=None,
    adapter_dirs: list[str] | None = None, weight_quant: str | None = None,
):
    """Weight-offload loader: returns (stacked_prefix, host_layers, spec).

    The first `resident` layers stack on device as usual; the remaining
    layers are pulled back to HOST memory (numpy pytrees) one at a time —
    the span's device footprint never exceeds the prefix plus one layer, so
    a server can serve a span larger than its HBM (reference FlexGen Policy
    weight percentages). `weight_quant` quantizes every layer (int8 halves
    / int4 quarters the host->device bytes streamed per step — the main
    lever on offloaded decode speed)."""
    import jax

    from bloombee_tpu.models import wquant
    from bloombee_tpu.models.auto import get_family
    from bloombee_tpu.utils.tree import stack_params

    reader = CheckpointReader(model_dir)
    family = get_family(reader.model_type())
    spec = family.spec_from_config_dict(reader.config)
    if spec.heterogeneous:
        raise ValueError("weight offload + heterogeneous spans unsupported")
    adapters = [LoraAdapter(d) for d in (adapter_dirs or [])]
    bits = {"int8": 8, "int4": 4}.get(weight_quant or "")
    prefix, host = [], []
    for i in range(start, end):
        params = family.load_block_params(reader, i, dtype=dtype)
        for adapter in adapters:
            params = adapter.merge_into(params, i)
        if bits:
            params = wquant.quantize_layer_params(params, bits)
        if i - start < resident:
            prefix.append(params)
        else:
            host.append(jax.device_get(params))
    stacked = stack_params(prefix) if prefix else None
    return stacked, host, spec


class LoraAdapter:
    """A PEFT-format LoRA adapter directory (adapter_config.json +
    adapter_model.safetensors)."""

    # our param name -> HF module suffix
    _TARGETS = {
        "q_proj": "self_attn.q_proj",
        "k_proj": "self_attn.k_proj",
        "v_proj": "self_attn.v_proj",
        "o_proj": "self_attn.o_proj",
        "gate_proj": "mlp.gate_proj",
        "up_proj": "mlp.up_proj",
        "down_proj": "mlp.down_proj",
    }

    def __init__(self, adapter_dir: str):
        d = pathlib.Path(adapter_dir)
        self.dir = d
        with open(d / "adapter_config.json") as f:
            cfg = json.load(f)
        import math

        r = cfg["r"]
        self.scaling = cfg["lora_alpha"] / (
            math.sqrt(r) if cfg.get("use_rslora") else r
        )
        files = sorted(d.glob("*.safetensors"))
        if not files:
            raise FileNotFoundError(f"no adapter safetensors in {d}")
        self._handles = [safe_open(f, framework="numpy") for f in files]
        self._key_to_handle = {
            k: h for h in self._handles for k in h.keys()
        }
        self.merged_tensors = 0

    def _find(self, layer_idx: int, target: str, which: str) -> str | None:
        suffix = f"layers.{layer_idx}.{target}.{which}.weight"
        for k in self._key_to_handle:
            if k.endswith(suffix):
                return k
        return None

    def _get(self, key: str) -> np.ndarray:
        return np.asarray(
            self._key_to_handle[key].get_tensor(key), dtype=np.float32
        )

    def span_factors(self, start: int, end: int, dtype=None) -> dict:
        """Stacked UNMERGED factors for blocks [start, end): per targeted
        projection, {"a": [L, in, r], "b": [L, r, out]} with the alpha/r
        scaling folded into b. This is the per-request adapter path
        (reference utils/peft.py `using_adapter` + LoraLinear): one base
        weight serves every adapter, the step adds (x a) b for the selected
        one. Layers the adapter doesn't target get zero factors."""
        import jax.numpy as jnp

        per_target: dict[str, dict] = {}
        for name, target in self._TARGETS.items():
            a_list: list = []
            b_list: list = []
            shapes = None
            for i in range(start, end):
                ka = self._find(i, target, "lora_A")
                kb = self._find(i, target, "lora_B")
                if ka is not None and kb is not None:
                    a = self._get(ka)  # PEFT A: [r, in]
                    b = self._get(kb)  # PEFT B: [out, r]
                    a_list.append(a.T)  # [in, r] for x @ a
                    b_list.append(b.T * self.scaling)  # [r, out]
                    shapes = (a.shape, b.shape)
                else:
                    a_list.append(None)
                    b_list.append(None)
            if shapes is None:
                continue
            (r, din), (dout, _) = shapes
            a_zero = np.zeros((din, r), np.float32)
            b_zero = np.zeros((r, dout), np.float32)
            a_stack = np.stack([a if a is not None else a_zero for a in a_list])
            b_stack = np.stack([b if b is not None else b_zero for b in b_list])
            per_target[name] = {
                "a": jnp.asarray(a_stack, dtype=dtype),
                "b": jnp.asarray(b_stack, dtype=dtype),
            }
        if not per_target:
            # distinguish "adapter targets other layers" (fine: this span
            # serves base weights, e.g. layers_to_transform adapters split
            # across servers) from "key layout mismatch" (a correctness
            # trap: NO server would ever apply the adapter)
            import re

            any_layer = any(
                re.search(
                    rf"layers\.\d+\.(?:{'|'.join(map(re.escape, self._TARGETS.values()))})\.lora_[AB]\.weight$",
                    k,
                )
                for k in self._key_to_handle
            )
            if not any_layer:
                raise ValueError(
                    f"adapter {self.dir} matched no tensors for ANY layer; "
                    f"adapter keys like "
                    f"{next(iter(self._key_to_handle), None)!r}"
                )
        return per_target

    def merge_into(self, params: dict, layer_idx: int) -> dict:
        import jax.numpy as jnp

        merged_here = 0
        for name, target in self._TARGETS.items():
            if name not in params:
                continue
            ka = self._find(layer_idx, target, "lora_A")
            kb = self._find(layer_idx, target, "lora_B")
            if ka is None or kb is None:
                continue
            a = self._get(ka)
            b = self._get(kb)
            delta = (b @ a) * self.scaling  # [out, in], as torch has it
            if name not in OUT_MAJOR_KEYS:
                delta = delta.T  # stored [in, out] (models/layout.py)
            params[name] = (
                params[name].astype(jnp.float32) + jnp.asarray(delta)
            ).astype(params[name].dtype)
            merged_here += 1
        if merged_here == 0:
            # silently serving base weights as "fine-tuned" would be a
            # correctness trap (fused-QKV families, or prefix-mismatched keys)
            raise ValueError(
                f"adapter {self.dir} matched no tensors for layer "
                f"{layer_idx}; param names {sorted(params)} vs adapter keys "
                f"like {next(iter(self._key_to_handle), None)!r}"
            )
        self.merged_tensors += merged_here
        return params


def load_adapter_factors(
    adapter_dir: str, start: int, end: int, dtype=None
) -> dict:
    """Unmerged stacked LoRA factors for a span (see
    LoraAdapter.span_factors) — the load half of per-request adapter
    switching."""
    return LoraAdapter(adapter_dir).span_factors(start, end, dtype=dtype)


def resolve_adapter(adapters: dict, name: str | None):
    """Shared adapter lookup: None -> base (no factors); unknown -> loud."""
    if name is None:
        return None
    try:
        return adapters[name]
    except KeyError:
        raise KeyError(
            f"unknown adapter {name!r}; serving "
            f"{sorted(adapters) or 'base only'}"
        ) from None


def load_client_params(model_dir: str, dtype=None) -> dict:
    """Embeddings + final norm + LM head (the client-side trio), plus any
    family extras (embedding layernorm, norm bias, tied heads)."""
    import jax.numpy as jnp

    from bloombee_tpu.models.auto import get_family

    reader = CheckpointReader(model_dir)
    family = get_family(reader.model_type())
    if family.client_loader is not None:
        return family.client_loader(reader, dtype=dtype)
    names = family.client_param_names()
    embed = jnp.asarray(reader.tensor(names["embed"]))
    norm = jnp.asarray(reader.tensor(names["norm"]))
    if reader.has(names["lm_head"]):
        head = jnp.asarray(reader.tensor(names["lm_head"])).T
    else:  # tied embeddings
        head = embed.T
    if dtype is not None:
        embed, norm, head = (
            embed.astype(dtype), norm.astype(dtype), head.astype(dtype)
        )
    return {"embed": embed, "norm": norm, "lm_head": head}
