"""shard_map helpers for the pipeline schedules.

The pipeline schedules (parallel/pipeline.py GPipe, parallel/schedule.py
1F1B) run inside a ``jax.shard_map`` that is MANUAL over the "pipe"
mesh axis only -- data/ctx/model axes stay under GSPMD so tensor
parallelism inside each stage needs no hand-written collectives.
"""

from functools import partial
from typing import Any

import jax

from realhf_tpu.parallel.mesh import PIPE_AXIS


def pipe_shard_map(f=None, *, mesh, in_specs, out_specs):
    """shard_map manual over the "pipe" axis only. Usable as a
    decorator (``@partial(pipe_shard_map, mesh=..., in_specs=...,
    out_specs=...)``) exactly like ``jax.shard_map``."""
    if f is None:
        return partial(pipe_shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return jax.shard_map(f, mesh=mesh, axis_names={PIPE_AXIS},
                         in_specs=in_specs, out_specs=out_specs)


def to_varying(x: Any):
    """Mark a pipe-replicated value as device-varying over "pipe" so it
    can mix with rotated state under the vma type system."""
    return jax.lax.pcast(x, (PIPE_AXIS,), to="varying")
