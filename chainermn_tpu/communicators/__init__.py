"""Communicator factory — analogue of ``chainermn.create_communicator``
(reference: ``chainermn/communicators/__init__.py``, unverified — mount
empty, see SURVEY.md).

ChainerMN shipped seven communicators that were all *allreduce algorithm
variants* over MPI/NCCL (naive, flat, hierarchical, two_dimensional,
single_node, non_cuda_aware, pure_nccl).  On TPU the algorithm choice is
XLA's job — it picks ring/tree/bidirectional schedules per mesh axis over
ICI/DCN — so those seven collapse into one ``tpu_xla`` backend plus a
``loopback`` for single-rank runs.  The legacy names are accepted as
aliases (with the mapping logged) so reference users can port launch
scripts unchanged.
"""

from __future__ import annotations

import warnings
from typing import Optional, Sequence

from ._obj_channel import DataSizeError
from .base import CommunicatorBase
from .loopback import LoopbackCommunicator
from .tpu_xla import TpuXlaCommunicator

_LEGACY_ALIASES = {
    # ChainerMN name      -> TPU-native behaviour
    "naive": "tpu_xla",
    "flat": "tpu_xla",
    "hierarchical": "tpu_xla",
    "two_dimensional": "tpu_xla",
    "single_node": "tpu_xla",
    "non_cuda_aware": "tpu_xla",
    "pure_nccl": "tpu_xla",
}


def create_communicator(
    communicator_name: str = "tpu_xla",
    devices: Optional[Sequence] = None,
    axis_name: str = "world",
    allreduce_grad_dtype=None,
    batched_copy: bool = True,  # accepted for parity; XLA always fuses
) -> CommunicatorBase:
    """Create a communicator.

    Args:
      communicator_name: ``"tpu_xla"`` (all devices, XLA collectives over
        ICI/DCN), ``"loopback"`` (size-1), or a legacy ChainerMN name
        (mapped to ``tpu_xla`` with a warning).
      devices: optional explicit device list (default: all ``jax.devices()``).
      axis_name: mesh axis name used for in-jit collectives.
      allreduce_grad_dtype: cast gradients to this dtype around the mean
        (ChainerMN's fp16 allreduce; use ``jnp.bfloat16`` on TPU).
      batched_copy: ignored — XLA fuses pack/cast/reduce automatically.
    """
    name = communicator_name
    if name in _LEGACY_ALIASES:
        warnings.warn(
            f"communicator {name!r} is a ChainerMN legacy alias; using "
            f"{_LEGACY_ALIASES[name]!r} (XLA chooses the collective "
            "algorithm per mesh axis)",
            stacklevel=2,
        )
        name = _LEGACY_ALIASES[name]

    if name == "loopback":
        dev = devices[0] if devices else None
        return LoopbackCommunicator(device=dev, axis_name=axis_name)
    if name == "tpu_xla":
        return TpuXlaCommunicator(
            devices=devices, axis_name=axis_name,
            grad_dtype=allreduce_grad_dtype,
        )
    raise ValueError(
        f"unknown communicator {communicator_name!r}; "
        f"choose from ['tpu_xla', 'loopback'] or legacy "
        f"{sorted(_LEGACY_ALIASES)}"
    )


__all__ = [
    "CommunicatorBase",
    "DataSizeError",
    "LoopbackCommunicator",
    "TpuXlaCommunicator",
    "create_communicator",
]


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> None:
    """Initialise the JAX multi-host runtime — the ``mpiexec -n N`` moment.

    ChainerMN's process model was MPI launch: one rank per GPU, world size
    fixed by ``mpiexec``.  The TPU-native model is one *process per host*
    (each driving its local chips), wired together by the JAX distributed
    runtime.  On Cloud TPU pods all arguments are auto-detected from the
    environment; elsewhere pass them explicitly — they correspond 1:1 to
    MPI's (coordinator ≈ rank-0 endpoint, num_processes ≈ world size,
    process_id ≈ rank).

    Call once per process BEFORE any other JAX API, then
    ``create_communicator("tpu_xla")`` sees the global device set
    (``comm.size`` = all chips in the pod, ``comm.inter_size`` = hosts).

    No-ops gracefully when the runtime is already initialised (so single-
    host runs and tests can call it unconditionally).
    """
    import jax
    from jax._src import xla_bridge

    if jax.distributed.is_initialized():
        return
    # Single-host convenience: with no explicit cluster spec there is
    # nothing to coordinate, and jax.distributed.initialize would raise if
    # the XLA backend is already up — let unconditional calls in tests and
    # single-process runs fall through to a no-op in that case.
    single_host = num_processes in (None, 1) and coordinator_address is None
    if single_host and xla_bridge.backends_are_initialized():
        return

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)


__all__.append("init_distributed")
