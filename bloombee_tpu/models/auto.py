"""Model family registry.

Port of /root/reference/src/bloombee/utils/auto_config.py:82-100: a registry
keyed by HF `model_type` dispatching config mapping, block param loading, and
client param names per family.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Callable

from bloombee_tpu.models.spec import ModelSpec

_REGISTRY: dict[str, "Family"] = {}


class Family:
    def __init__(
        self,
        name: str,
        spec_fn: Callable[[Any], ModelSpec],
        block_keys: dict[str, tuple[str, bool]] | None = None,
        layer_prefix: str = "model.layers",
        client_names: dict[str, str] | None = None,
        convert_block: Callable | None = None,
        loader: Callable | None = None,
        client_loader: Callable | None = None,
        refine_spec: Callable | None = None,  # (spec, reader) -> spec: what
        # only the checkpoint says (deepseek_v2: the router's width, the
        # experts held)
    ):
        self.name = name
        self._spec_fn = spec_fn
        self.block_keys = block_keys or {}
        self.layer_prefix = layer_prefix
        self._client_names = client_names or {
            "embed": "model.embed_tokens.weight",
            "norm": "model.norm.weight",
            "lm_head": "lm_head.weight",
        }
        self._convert_block = convert_block
        self._loader = loader
        self.client_loader = client_loader
        self._refine_spec = refine_spec

    def spec_from_reader(self, reader) -> ModelSpec:
        """The spec of the checkpoint `reader` is open on."""
        spec = self.spec_from_config_dict(reader.config)
        if self._refine_spec is not None:
            spec = self._refine_spec(spec, reader)
        return spec

    def spec_from_config_dict(self, config: dict) -> ModelSpec:
        return self._spec_fn(SimpleNamespace(**config))

    def client_param_names(self) -> dict[str, str]:
        return self._client_names

    def load_block_params(self, reader, layer_idx: int, dtype=None) -> dict:
        if self._loader is not None:
            return self._loader(reader, layer_idx, dtype=dtype)
        tensors = {}
        for hf_key in self.block_keys:
            full = f"{self.layer_prefix}.{layer_idx}.{hf_key}"
            tensors[hf_key] = reader.tensor(full)
        if self._convert_block is not None:
            return self._convert_block(tensors, dtype=dtype)
        raise NotImplementedError(self.name)


def register_family(family: Family) -> None:
    _REGISTRY[family.name] = family


def get_family(model_type: str) -> Family:
    if model_type not in _REGISTRY:
        raise KeyError(
            f"unknown model family {model_type!r}; known: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[model_type]


def spec_from_hf_config(config: Any) -> ModelSpec:
    return get_family(config.model_type)._spec_fn(config)


def spec_from_config_dict(config: dict) -> ModelSpec:
    return get_family(config.get("model_type", "llama")).spec_from_config_dict(
        config
    )


# ---------------------------------------------------------------- built-ins
def _register_builtins() -> None:
    from bloombee_tpu.models.llama.block import (
        HF_BLOCK_KEYS as LLAMA_KEYS,
        convert_hf_block_params as llama_convert,
    )
    from bloombee_tpu.models.llama.config import llama_spec_from_hf

    register_family(
        Family(
            "llama",
            llama_spec_from_hf,
            LLAMA_KEYS,
            convert_block=llama_convert,
        )
    )
    # side-effect registrations
    import bloombee_tpu.models.afmoe  # noqa: F401
    import bloombee_tpu.models.bloom  # noqa: F401
    import bloombee_tpu.models.deepseek_v2  # noqa: F401
    import bloombee_tpu.models.falcon  # noqa: F401
    import bloombee_tpu.models.falcon_h1  # noqa: F401
    import bloombee_tpu.models.gemma2  # noqa: F401
    import bloombee_tpu.models.gemma4  # noqa: F401
    import bloombee_tpu.models.kimi_linear  # noqa: F401
    import bloombee_tpu.models.mistral  # noqa: F401
    import bloombee_tpu.models.mixtral  # noqa: F401
    import bloombee_tpu.models.nemotron_h  # noqa: F401
    import bloombee_tpu.models.phi4flash  # noqa: F401
    import bloombee_tpu.models.qwen2  # noqa: F401
    import bloombee_tpu.models.qwen3  # noqa: F401
    import bloombee_tpu.models.qwen3_next  # noqa: F401


_register_builtins()
