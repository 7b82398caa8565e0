"""Device meshes for SPMD parallelism.

The mesh is the TPU-native replacement for the reference's process-group
plumbing (reference: torch.distributed init in train/torch/config.py,
NCCL groups in util/collective/collective_group/nccl_collective_group.py):
instead of wiring communicators between processes, we lay devices out on
a named mesh and let XLA/GSPMD insert collectives that ride ICI.

Axis conventions (the "How to Scale Your Model" recipe):
  data   — data parallelism (batch split; gradient psum)
  fsdp   — fully-sharded data parallelism (params/optimizer sharded,
           all-gathered per layer; arXiv 2004.13336 weight-update sharding)
  model  — tensor parallelism (attention heads / mlp hidden split)
  seq    — sequence/context parallelism (ring attention, Ulysses)
  pipe   — pipeline stages
  expert — MoE expert parallelism
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("pipe", "data", "fsdp", "seq", "expert", "model")


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Axes of size 1 are kept (harmless to GSPMD)."""
    data: int = 1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    def axes(self) -> Dict[str, int]:
        return {
            "pipe": self.pipe, "data": self.data, "fsdp": self.fsdp,
            "seq": self.seq, "expert": self.expert, "model": self.model,
        }

    @property
    def size(self) -> int:
        return math.prod(self.axes().values())

    @staticmethod
    def for_devices(n: int, *, model: int = 1, seq: int = 1,
                    pipe: int = 1, expert: int = 1,
                    fsdp: Optional[int] = None) -> "MeshSpec":
        """Fill the data/fsdp axes with whatever devices remain."""
        rest = n // (model * seq * pipe * expert)
        if rest * model * seq * pipe * expert != n:
            raise ValueError(
                f"{n} devices not divisible by model*seq*pipe*expert="
                f"{model * seq * pipe * expert}")
        if fsdp is None:
            return MeshSpec(data=rest, model=model, seq=seq, pipe=pipe,
                            expert=expert)
        if rest % fsdp:
            raise ValueError(f"residual {rest} not divisible by fsdp={fsdp}")
        return MeshSpec(data=rest // fsdp, fsdp=fsdp, model=model, seq=seq,
                        pipe=pipe, expert=expert)


def make_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Build a jax.sharding.Mesh laid out so the innermost (most
    communication-heavy) axes are contiguous in device order — on a TPU
    slice contiguous device ids are ICI neighbors, so `model`/`seq`
    collectives ride the fastest links while `pipe`/`data` span the
    slower dimension (and DCN on multi-slice)."""
    import jax
    if devices is None:
        devices = jax.devices()
    if len(devices) < spec.size:
        raise ValueError(
            f"mesh needs {spec.size} devices, have {len(devices)}")
    axes = spec.axes()
    shape = tuple(axes[name] for name in AXIS_ORDER)
    arr = np.asarray(devices[: spec.size]).reshape(shape)
    return jax.sharding.Mesh(arr, AXIS_ORDER)


def single_device_mesh():
    """A trivial mesh for one chip (bench on the single real TPU)."""
    return make_mesh(MeshSpec())


def mesh_axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def mesh_axes(mesh, *names: str):
    """Those of `names` that are axes of this mesh, as one
    PartitionSpec entry (None when the mesh has none of them), and the
    product of their sizes."""
    present = tuple(n for n in names if n in mesh.axis_names)
    size = math.prod(mesh.shape[n] for n in present)
    return (present or None), size


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap over DCN.

    reference: train/v2/jax/config.py:29 _setup_jax_tpu_environment —
    each train worker calls jax.distributed.initialize so every host's
    jax sees the full pod's devices. No-op when already initialized or
    single-process.
    """
    import jax
    if num_processes in (None, 0, 1):
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
    except RuntimeError as exc:
        # jax's own wording for a second call in one process
        if "should only be called once" not in str(exc):
            raise
