"""Named-axis collectives: the TPU-native communication backend.

This is the replacement surface for mshadow-ps `ISharedModel` (SURVEY.md
§2.10): where the reference pushes/pulls per-tensor gradients through a
parameter server (src/updater/async_updater-inl.hpp:94-143), the TPU design
expresses the same dataflow as XLA collectives over mesh axes — all-reduce
over ICI inside a slice, DCN across slices — and lets the latency-hiding
scheduler overlap them with compute (the reference's per-tensor priority
scheme, src/updater/updater_impl-inl.hpp:84, done by the compiler instead).

These wrappers exist so higher layers (trainer, ring attention, pipeline)
speak one vocabulary; each is a direct jax.lax collective.

What the collectives cost at run time is in the profiler's trace
(``profile_dir``, ``tools/trace_layers.py``).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax import lax

AxisName = Union[str, Sequence[str]]


def psum(x, axis_name: AxisName):
    """All-reduce sum over a mesh axis (gradient sync; replaces PS Push+Pull
    of summed gradients, src/updater/async_updater-inl.hpp:101-131)."""
    return lax.psum(x, axis_name)


def pmean(x, axis_name: AxisName):
    """All-reduce mean (metric aggregation across data shards)."""
    return lax.pmean(x, axis_name)


def all_gather(x, axis_name: AxisName, *, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` from every device on the mesh axis
    (replaces the `fullc_gather` activation allgather,
    src/updater/async_updater-inl.hpp:67-92)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName, *, axis: int = 0):
    """Reduce-scatter: sum across the axis, each device keeps one shard
    (the ZeRO / update_on_server gradient path)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute(x, axis_name: AxisName, perm):
    """Point-to-point permutation over ICI neighbors (ring steps)."""
    return lax.ppermute(x, axis_name, perm)


def ring_shift(x, axis_name: str, shift: int = 1):
    """Rotate shards around the ring: device i's value goes to i+shift."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: AxisName, *, split_axis: int, concat_axis: int):
    """All-to-all redistribution (Ulysses-style sequence<->head reshard)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)
