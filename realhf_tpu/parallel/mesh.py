"""Device meshes and the per-model parallelism context.

TPU-native replacement for reference ``realhf/base/topology.py``
(`ProcessTopology`/`ParallelGrid`) and the ambient parallelism globals
in ``realhf/base/constants.py:170-513``: each model (one node of the
dataflow graph) owns a `jax.sharding.Mesh` over a slice of the
device fleet plus a `ParallelismConfig`. GSPMD + pjit derive all
collectives from shardings, so there are no explicit communication
groups to build -- the mesh IS the topology.

Axis convention (stable across the framework):
  - "pipe":  pipeline stages (GPipe microbatch rotation, see
             parallel/pipeline.py; blocks are layer-sharded over it).
  - "data":  data parallelism over packed sequence streams.
  - "model": tensor parallelism; with ``sequence_parallel`` the
             sequence dim of activations is also sharded over this
             axis in norm/residual regions (Megatron-SP analog,
             free under GSPMD).
"""

import contextlib
import dataclasses
import threading
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from realhf_tpu.api.config import ModelName

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
CTX_AXIS = "ctx"  # context parallelism (ring attention over sequence)
MODEL_AXIS = "model"
MESH_AXES = (PIPE_AXIS, DATA_AXIS, CTX_AXIS, MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class ParallelismConfig:
    """3D parallelism degrees of one model, mirroring reference
    ``api/quickstart/model.py:15`` (ParallelismConfig)."""
    data_parallel_size: int = 1
    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    # ring attention over the sequence dim (the reference's missing
    # context parallelism, megatron.py:60-61 TODO)
    context_parallel_size: int = 1
    sequence_parallel: bool = False
    gradient_checkpointing: bool = False
    # Pipeline microbatch count when pipeline_parallel_size > 1
    # (0 = auto, schedule-dependent: 2*pp for gpipe, 4*pp for 1f1b --
    # parallel/schedule.default_microbatches); not part of the weight
    # layout (same_layout ignores it).
    pipeline_microbatches: int = 0
    # Tick schedule for pipeline-parallel TRAINING: "1f1b" (default --
    # explicit instruction streams, custom-VJP backward pipeline,
    # bounded residuals, masked bubble ticks; parallel/schedule.py) or
    # "gpipe" (lockstep rotation scan with autodiff backward;
    # parallel/pipeline.py). Inference-only forwards always use the
    # GPipe rotation (no backward to schedule). Not part of the weight
    # layout (same_layout ignores it).
    pipeline_schedule: str = "1f1b"
    # Tensor-parallel degree of the DECODE VIEW used for generation on
    # a pipeline- or context-parallel mesh (engine.decode_engine):
    # weights reshard onto a collapsed (world/gen_tp) x gen_tp dp x tp
    # mesh over the same devices. 0 = inherit tensor_parallel_size.
    # Not part of the weight layout (same_layout ignores it).
    gen_tp_size: int = 0

    def __post_init__(self):
        if self.sequence_parallel and self.tensor_parallel_size == 1:
            object.__setattr__(self, "sequence_parallel", False)
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pipeline_schedule must be 'gpipe' or '1f1b', got "
                f"{self.pipeline_schedule!r}")

    @property
    def world_size(self) -> int:
        return (self.data_parallel_size * self.tensor_parallel_size *
                self.pipeline_parallel_size * self.context_parallel_size)

    def same_layout(self, other: "ParallelismConfig") -> bool:
        """Same device-placement layout (ignores flags like
        gradient_checkpointing that do not affect weight sharding)."""
        return (self.data_parallel_size == other.data_parallel_size
                and self.tensor_parallel_size == other.tensor_parallel_size
                and self.pipeline_parallel_size == other.pipeline_parallel_size
                and self.context_parallel_size == other.context_parallel_size
                and self.sequence_parallel == other.sequence_parallel)

    def __str__(self):
        s = (f"d{self.data_parallel_size}t{self.tensor_parallel_size}"
             f"p{self.pipeline_parallel_size}")
        if self.context_parallel_size > 1:
            s += f"c{self.context_parallel_size}"
        if self.sequence_parallel:
            s += "s"
        if self.gen_tp_size:
            s += f"g{self.gen_tp_size}"
        return s


def parse_parallelism(name: str) -> ParallelismConfig:
    """Parse the reference's ``d$Np$Pm$M`` allocation shorthand
    (``experiments/common/utils.py:201``), e.g. "d4t2" or "d2t2p2".
    Axis letters: d = data, t = tensor (m also accepted), p = pipeline;
    trailing "s" enables sequence parallelism.
    """
    import re
    s = name.strip()
    tokens = re.findall(r"([dtmpcg])(\d+)|(s)(?!\d)", s)
    consumed = "".join(t[0] + t[1] + t[2] for t in tokens)
    sizes = {"d": 1, "t": 1, "p": 1, "c": 1, "g": 0}
    seq_par = False
    for axis, num, sp in tokens:
        if sp:
            seq_par = True
            continue
        key = "t" if axis == "m" else axis  # m = model = tensor
        sizes[key] = int(num)
    if consumed != s or not tokens:
        raise ValueError(f"Cannot parse parallelism spec `{name}`; "
                         "expected e.g. d4t2, d4p1m2, d2t2p1, d1t8s "
                         "(any axis order; m is an alias for t).")
    return ParallelismConfig(
        data_parallel_size=sizes["d"],
        tensor_parallel_size=sizes["t"],
        pipeline_parallel_size=sizes["p"],
        context_parallel_size=sizes["c"],
        sequence_parallel=seq_par,
        gen_tp_size=sizes["g"])


def default_devices() -> List:
    """Device fleet used when no explicit slice is given: every device
    of the process's platform (``JAX_PLATFORMS``)."""
    return list(jax.devices())


def make_mesh(parallel: ParallelismConfig,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build the (pipe, data, model) mesh for one model over the given
    device slice (defaults to all local devices).

    Device ordering follows jax's default enumeration, which on real
    TPU slices keeps ICI neighbors adjacent -- the "model" (innermost)
    axis therefore rides the fastest links, matching the reference's
    placement of TP on NVLink (`docs/source/impl.rst`).
    """
    devices = list(devices) if devices is not None else default_devices()
    if parallel.world_size != len(devices):
        raise ValueError(
            f"Parallelism {parallel} needs {parallel.world_size} devices, "
            f"got {len(devices)}.")
    arr = np.array(devices).reshape(
        parallel.pipeline_parallel_size,
        parallel.data_parallel_size,
        parallel.context_parallel_size,
        parallel.tensor_parallel_size)
    return Mesh(arr, MESH_AXES)


@dataclasses.dataclass
class MeshContext:
    """Everything parallelism-related about one model instance:
    replaces the reference's `ParallelGrid` + `constants.model_scope`
    ambient state with an explicit object."""
    model_name: ModelName
    mesh: Mesh
    parallel: ParallelismConfig

    @property
    def dp_size(self) -> int:
        return self.parallel.data_parallel_size

    @property
    def tp_size(self) -> int:
        return self.parallel.tensor_parallel_size

    @property
    def pp_size(self) -> int:
        return self.parallel.pipeline_parallel_size

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))


# ----------------------------------------------------------------------
# Optional ambient registry. The runtime registers one MeshContext per
# model and switches scope around interface calls, mirroring
# `constants.model_scope` (reference constants.py:170) for code that
# cannot take the context as an argument.
# ----------------------------------------------------------------------
_local = threading.local()
_contexts: Dict[ModelName, MeshContext] = {}


def register_context(ctx: MeshContext):
    _contexts[ctx.model_name] = ctx


def clear_contexts():
    _contexts.clear()


@contextlib.contextmanager
def model_scope(model_name: ModelName):
    prev = getattr(_local, "active", None)
    _local.active = _contexts[model_name]
    try:
        yield _local.active
    finally:
        _local.active = prev


def current_context() -> MeshContext:
    ctx = getattr(_local, "active", None)
    if ctx is None:
        raise RuntimeError("No active model scope; use model_scope(...).")
    return ctx
