"""Kernels, latent attention: per solo prefill-chunk run of
`span_step_packed`, the summed self time of the ops under `mla_q`, `mla_kv`,
`mla_absorb`, `mla_attention` and `latent_io` (`cellbench/mlatrace.py`);
median."""

from cellbench import mlatrace


def read(ctx: dict):
    got = mlatrace.reduced(ctx)
    return got and got["chunk_mla_ms_p50"]
