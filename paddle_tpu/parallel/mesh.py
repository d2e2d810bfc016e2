"""Device mesh & sharding — the replacement for the reference's entire
distribution stack: the intra-node thread-ring of MultiGradientMachine
(reference: paddle/gserver/gradientmachines/MultiGradientMachine.h:44-120) and
the inter-node parameter servers (reference: paddle/pserver/ParameterServer2.h,
go/pserver).

Design: one global `jax.sharding.Mesh` with named axes

    data   — data parallelism (batch axis).  Gradient psum rides ICI
             AllReduce; there is no parameter server to push/pull.
    model  — tensor/model parallelism for wide layers & sharded embeddings
             (replaces ParallelNeuralNetwork per-layer device placement and
             the row-sharded sparse tables on pservers).

Parameters/optimizer state are replicated over `data` (or sharded over
`model` when a layer opts in); batches are sharded over `data` on the leading
axis.  XLA inserts the collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    data: int = -1  # -1 = all remaining devices
    model: int = 1


# ---------------------------------------------------------------------------
# Multi-process process group — jax.distributed with a coordination-service
# fallback shim.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProcessGroup:
    """Membership of a multi-process run.

    backend:
      * ``"jax-distributed"`` — a real jax.distributed runtime was formed;
        jax.devices() is the GLOBAL device set and in-program collectives
        cross processes over ICI/DCN.
      * ``"shim"`` — the dev-container fallback (CPU-only platforms
        where jax.distributed cannot form a backend): each
        process keeps its local devices and cross-process reduction rides
        the master coordination service instead (trainer/elastic.py's
        pass-fence + task-result reduce).
      * ``"single"`` — no multi-process environment configured.
    """

    num_processes: int = 1
    process_id: int = 0
    coordinator: Optional[str] = None
    backend: str = "single"

    def __bool__(self) -> bool:  # truthy == genuinely multi-process
        return self.num_processes > 1


_process_group = ProcessGroup()


def init_process_group(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    use_jax_distributed: Optional[bool] = None,
) -> ProcessGroup:
    """Join (or declare) the process group.  Arguments default from the
    launcher environment (``PADDLE_TPU_COORDINATOR`` etc.).

    ``use_jax_distributed``: None (default) consults the
    ``PADDLE_TPU_DIST_BACKEND=jax`` environment switch — on TPU pods the
    real runtime is what you want, but on the CPU dev container calling
    ``jax.distributed.initialize`` would hang against a coordinator that
    can never form a device backend, so the shim is the default there."""
    global _process_group
    import os

    from paddle_tpu import launcher as _launcher

    coordinator = coordinator or os.environ.get(_launcher.ENV_COORD)
    if num_processes is None:
        num_processes = int(os.environ.get(_launcher.ENV_NPROC, "0") or 0)
    if process_id is None:
        process_id = int(os.environ.get(_launcher.ENV_PROC_ID, "0") or 0)
    if not coordinator or num_processes <= 1:
        _process_group = ProcessGroup()
        return _process_group
    if use_jax_distributed is None:
        use_jax_distributed = (
            os.environ.get("PADDLE_TPU_DIST_BACKEND", "") == "jax"
        )
    backend = "shim"
    if use_jax_distributed:
        import logging

        import jax

        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
            backend = "jax-distributed"
        except (AttributeError, NotImplementedError, RuntimeError, ValueError) as exc:
            # no distributable backend: fall back to the shim — but the
            # operator EXPLICITLY asked for the real runtime, so
            # say loudly that they are not getting it (a silent shim on a
            # pod means N unsynchronized replicas, not one job)
            logging.getLogger("paddle_tpu.parallel").warning(
                "PADDLE_TPU_DIST_BACKEND=jax requested but "
                "jax.distributed.initialize failed (%s: %s); falling back "
                "to the coordination-service shim — in-program collectives "
                "will NOT cross processes", type(exc).__name__, exc,
            )
            backend = "shim"
    _process_group = ProcessGroup(
        num_processes=num_processes,
        process_id=process_id,
        coordinator=coordinator,
        backend=backend,
    )
    return _process_group


def current_process_group() -> ProcessGroup:
    return _process_group


_default_mesh: Optional[Mesh] = None


def make_mesh(
    data: int = -1,
    model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if data == -1:
        assert n % model == 0, f"{n} devices not divisible by model={model}"
        data = n // model
    assert data * model == n, f"mesh {data}x{model} != {n} devices"
    arr = np.array(devs).reshape(data, model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


def get_default_mesh() -> Optional[Mesh]:
    return _default_mesh


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) axis across the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def shard_batch(batch, mesh: Optional[Mesh]):
    """Place a Batch pytree so every leaf's leading axis is split over the
    data axis (the feeder guarantees batch % data-size == 0)."""
    if mesh is None:
        return batch
    sh = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)
