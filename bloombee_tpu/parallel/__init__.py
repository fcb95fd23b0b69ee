"""Parallelism over the device mesh: tp / dp / sp / pp.

The reference's parallelism checklist (SURVEY.md section 2.8) mapped to
TPU-native constructs:

- tensor parallelism: Megatron-style sharded projections with explicit psum
  under shard_map (replaces FlexgenLlamaTensorParallel's per-device CUDA
  streams + NCCL all-reduce, flexgen_tensor_parallel.py:172-828) — rides ICI.
- sequence/context parallelism: ring attention over the "sp" axis (ppermute
  of KV blocks + online softmax) AND Ulysses all-to-all head/sequence
  exchange — the capability the reference LACKS (SURVEY.md section 5
  long-context) and handles only by host offload.
- data parallelism: batch sharding over "dp".
- pipeline parallelism: GPipe micro-batch schedule over the "pp" axis inside
  one jit (the swarm-level span pipeline remains inter-host over the wire).
"""

from bloombee_tpu.parallel.mesh import make_mesh, MeshConfig
from bloombee_tpu.parallel.ring_attention import ring_attention
from bloombee_tpu.parallel.ulysses import ulysses_attention
from bloombee_tpu.parallel.spmd import (
    shard_span_params,
    spmd_block_forward,
    spmd_span_forward,
)

__all__ = [
    "make_mesh",
    "MeshConfig",
    "ring_attention",
    "ulysses_attention",
    "shard_span_params",
    "spmd_block_forward",
    "spmd_span_forward",
]
