"""Device mesh creation and ``dev=`` spec parsing.

Replaces the reference's device-thread spawning (CXXNetThreadTrainer dev
parsing, src/nnet/nnet_impl-inl.hpp:32-51): ``dev=gpu:0-3`` meant four GPU
worker threads; here it selects devices for a 1-D data mesh (higher-dim
meshes for tensor/pipeline parallelism are built by passing axis specs).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh


def parse_device_spec(spec: str) -> Tuple[str, List[int]]:
    """Parse ``cpu`` / ``gpu`` / ``tpu`` / ``tpu:0-3`` / ``gpu:0,2`` into
    (kind, device_ids). Empty id list means "all available"."""
    if ":" not in spec:
        return spec, []
    kind, ids = spec.split(":", 1)
    if "-" in ids:
        a, b = ids.split("-")
        return kind, list(range(int(a), int(b) + 1))
    return kind, [int(x) for x in ids.split(",")]


def backend_initialized() -> bool:
    """True when a jax backend is already live in this process. Peeks at
    jax's internal registry, so the check itself initializes nothing."""
    from jax._src import xla_bridge as xb
    return bool(xb._backends)


def ensure_platform(kind: str) -> None:
    """Hold ``dev = <kind>`` against the backend this process runs on.
    ``cpu`` pins the CPU backend
    when none is live yet, so ``dev = cpu`` selects the CPU even on a
    machine whose default is an accelerator. Afterwards the backend must
    be the one the config named — ``tpu`` needs a TPU, ``gpu`` (the
    reference-era spelling, doc/migration.md) any accelerator, ``cpu`` the
    CPU — and anything else raises, naming what was found: a run never
    lands on a device the config did not ask for."""
    if kind not in ("cpu", "tpu", "gpu"):
        raise ValueError("dev: unknown device kind %r (cpu, tpu or gpu)"
                         % kind)
    if kind == "cpu" and not backend_initialized():
        jax.config.update("jax_platforms", "cpu")
    found = jax.default_backend()
    if found != kind and (kind != "gpu" or found == "cpu"):
        raise RuntimeError(
            "dev = %s requested, but this process runs on the %s backend "
            "(%d x %s; jax supports one platform per process)"
            % (kind, found, len(jax.devices()),
               jax.devices()[0].device_kind))


def create_mesh(device_ids: Optional[Sequence[int]] = None,
                axes: Tuple[str, ...] = ("data",),
                shape: Optional[Tuple[int, ...]] = None) -> Mesh:
    """Create a mesh over the given devices (default: all).

    axes/shape allow multi-axis meshes, e.g. axes=("data", "model"),
    shape=(4, 2). A 1-D data mesh reproduces the reference's data-parallel
    topology with ICI all-reduce instead of the PS.
    """
    devs = jax.devices()
    if device_ids:
        id_map = {d.id: d for d in devs}
        if all(i in id_map for i in device_ids):
            devs = [id_map[i] for i in device_ids]
        elif jax.process_count() > 1 and len(device_ids) <= len(devs):
            # multi-process runs have non-contiguous global device ids
            # (each process numbers its own block), so `dev=tpu:0-7` style
            # specs select positionally there
            devs = devs[: len(device_ids)]
        else:
            raise ValueError(
                "dev: device ids %s requested, but this process has only "
                "%s" % (list(device_ids), sorted(id_map)))
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axes) - 1)
    arr = np.array(devs[: int(np.prod(shape))]).reshape(shape)
    return Mesh(arr, axes)
