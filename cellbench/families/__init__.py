"""A model family is ONE FILE here, found by a configuration's `model_type`
the way `cellbench/metrics/<name>.py` is found by a metric's name: a later PR
brings a family the harness has never seen by adding
`cellbench/families/<model_type>.py` and edits no file that is there
(PERF.md, "Adding a configuration"). Nothing outside this directory reads
`model_type` or knows a tensor's name. A family file exports:

  the checkpoint's plan (cellbench/checkpoint.py writes it from --seed)
    layer_tensors(config, layer)  [(name, torch-layout shape[, fill])]
    client_tensors(config)        the same for the client's file (embedding,
                                  final norm, head; a tied head is left out)
    a fill is "bits" (the default: random sign and mantissa, magnitude
    2**-9..2**-6), "ones", or a range (checkpoint.py: `fill_values`)
  the plain reference's layer and its two ends (cellbench/reference.py loops)
    layer_params(tensors, config, layer)   one layer file's tensors by name
                                           -> the leaves layer_forward takes
    layer_forward(p, config, hidden [T, D], positions [T]) -> hidden [T, D]
    embed(client, config, ids) -> float32 [..., D]
    logits_rows(client, config, hidden_rows [R, D]) -> float32 [R, V]
    INT8_KEEPS (optional)  leaves of layer_params the int8 control leaves
                           alone beside every array of fewer than two
                           dimensions
  the roofline's needs (metrics/step_roofline.py, chunk_roofline.py)
    decode_step_needs(config, rows, context)  {"bytes", "flops", ...}
    chunk_needs(config, rows, context)
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def path_of(config: dict) -> pathlib.Path:
    """The file that holds the configuration's family, there or not."""
    return HERE / f"{config['model_type']}.py"


@functools.lru_cache(maxsize=None)
def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "cellbench_family_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def of(config: dict):
    """The family module of a configuration, found by its `model_type`."""
    path = path_of(config)
    if not path.exists():
        raise LookupError(
            f"no family file cellbench/families/{path.name} for model_type "
            f"{config['model_type']!r}: add it (what it exports: "
            f"cellbench/families/__init__.py)")
    return _load(path)
