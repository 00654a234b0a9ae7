"""Pipeline parallelism: GPipe-style microbatch pipelining over a ``pipe``
mesh axis.

Green-field for the TPU build (the reference has no model partitioning at
all, SURVEY.md §2.9 "Not present"). The design is the standard TPU
collective-permute pipeline: stage s lives on device s of the ``pipe`` axis;
activations hop one ICI neighbor per tick via ppermute; a scan over
n_micro + n_stages - 1 ticks drains the bubble. The whole schedule is one
jitted program, so XLA overlaps the hop with the next microbatch's compute.

Constraint (documented, checked): stage boundaries must share one activation
shape — stages are "equal-width", e.g. repeated blocks of a deep MLP/resnet
trunk. That is the shape-uniformity XLA needs to trace one stage body for
all devices.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import collectives
from ._compat import shard_map


def stage_sharding(mesh: Mesh, axis: str = "pipe") -> NamedSharding:
    """Sharding for stacked per-stage params: leading dim = stage index."""
    return NamedSharding(mesh, P(axis))


def _pipeline_local(params, x, *, axis_name: str, n_micro: int,
                    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray]):
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    # local slice of the stacked stage params: leading dim 1 -> this stage
    params = jax.tree.map(lambda p: p[0], params)
    cur = jnp.zeros_like(x[0])
    # forward hop: stage s -> s+1 (no wraparound; device 0 ingests fresh
    # microbatches, so its incoming edge is unused)
    perm = [(i, i + 1) for i in range(n - 1)]

    # finished microbatches leave as scan OUTPUTS, not an in-carry buffer
    # (the carry is AD-stashed per tick — see _pipeline_local_switch)
    def tick(cur, t):
        x_t = lax.dynamic_index_in_dim(x, jnp.clip(t, 0, n_micro - 1),
                                       axis=0, keepdims=False)
        inp = jnp.where(idx == 0, x_t, cur)
        y = stage_fn(params, inp)
        done = (t - (n - 1) >= 0) & (idx == n - 1)
        y_out = jnp.where(done, y, 0.0)
        cur = collectives.ppermute(y, axis_name, perm)
        return cur, y_out

    _, ys = lax.scan(tick, cur, jnp.arange(n_micro + n - 1))
    # ticks n-1 .. n-1+n_micro hold microbatches 0..n_micro in order on
    # the last stage (zeros elsewhere); psum broadcasts them
    return collectives.psum(ys[n - 1: n - 1 + n_micro], axis_name)


def _pipeline_local_switch(params, x, state0=None, *, axis_name: str,
                           n_micro: int, stage_fns, state_masks=None,
                           data_axis=None):
    """Like _pipeline_local, but heterogeneous stages: every device traces
    all stage bodies once and lax.switch selects its own by pipeline rank.
    All bodies map a (micro_batch, F) padded boundary vector to another —
    F = widest stage boundary — so the ppermute hop and the scan carry stay
    shape-uniform even when the underlying activations are not.

    With ``state0`` (an (S,) vector of non-gradient layer state, e.g. BN
    running stats), stage bodies take and return the state vector too:
    each device chains its OWN stage's slots across its microbatches (EMA
    order matches single-device sequential batches) and the final vector
    combines the per-stage slots via ``state_masks`` (a (n_stages, S)
    ownership mask) with a psum over the pipe axis; ``data_axis`` names a
    composed data axis to pmean per-shard statistics over."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    cur = jnp.zeros_like(x[0])
    perm = [(i, i + 1) for i in range(n - 1)]
    with_state = state0 is not None

    # Finished microbatches leave the scan as per-tick OUTPUTS (ys), not
    # as an in-carry output buffer: anything riding the carry is stashed
    # by AD at EVERY tick (an O(n_micro^2 * F) activation bill measured
    # in TestPipelineMemoryProof); a scan output is written once.
    def tick(carry, t):
        cur, st = carry
        x_t = lax.dynamic_index_in_dim(x, jnp.clip(t, 0, n_micro - 1),
                                       axis=0, keepdims=False)
        inp = jnp.where(idx == 0, x_t, cur)
        # stage `idx` works on microbatch t - idx at tick t (clipped while
        # the bubble fills/drains; those results are masked out anyway)
        micro_id = jnp.clip(t - idx, 0, n_micro - 1)
        if with_state:
            y, st_new = lax.switch(idx, stage_fns, params, inp, micro_id,
                                   st)
            # only commit state from real microbatches: bubble ticks run on
            # zeros and drain ticks would re-run (and re-EMA) the last one
            real = (t - idx >= 0) & (t - idx < n_micro)
            st = jnp.where(real, st_new, st)
        else:
            y = lax.switch(idx, stage_fns, params, inp, micro_id)
        done = (t - (n - 1) >= 0) & (idx == n - 1)
        y_out = jnp.where(done, y, 0.0)
        cur = collectives.ppermute(y, axis_name, perm)
        return (cur, st), y_out

    st0 = state0 if with_state else jnp.zeros((0,), x.dtype)
    (_, st), ys = lax.scan(tick, (cur, st0),
                           jnp.arange(n_micro + n - 1))
    # ticks n-1 .. n-1+n_micro hold microbatches 0..n_micro in order on
    # the last stage (zeros elsewhere); psum broadcasts them
    out = collectives.psum(ys[n - 1: n - 1 + n_micro], axis_name)
    if not with_state:
        return out
    own = lax.dynamic_index_in_dim(state_masks, idx, axis=0,
                                   keepdims=False)
    st = collectives.psum(jnp.where(own, st, 0.0), axis_name)
    if data_axis is not None:
        st = collectives.pmean(st, data_axis)
    return out, st


def pipeline_apply_stages(stage_fns, params, x, mesh: Mesh, *,
                          axis: str = "pipe", batch_spec=None,
                          params_spec=None, state0=None, state_masks=None):
    """Heterogeneous-stage GPipe over the mesh's ``axis``.

    stage_fns: one callable per stage, each
               (params, padded, micro_id) -> padded where padded is
               (micro_batch, F) — the stage slices its real input out of
               the padded vector and re-pads its output. micro_id is the
               traced index of the microbatch being processed (for
               per-microbatch rng folds in stochastic layers)
    params:    pytree passed to every stage. By default replicated over
               ``axis`` (each body indexes only its own layers' entries);
               with ``params_spec`` (e.g. P(axis, None) for a stage-packed
               (n_stages, F_p) array) it is SHARDED over the pipe axis and
               each body receives only its own rank's shard — per-device
               parameter ownership with zero parameter comm
    x:         (n_micro, micro_batch, F) padded input microbatches
    batch_spec: optional mesh axis name to keep the micro_batch dim sharded
               on (data parallelism composed with the pipeline)

    Returns (n_micro, micro_batch, F), replicated over ``axis``.
    With ``state0`` + ``state_masks`` (non-gradient layer state, e.g. BN
    running stats — see _pipeline_local_switch) the stage bodies take and
    return the (S,) state vector as a fourth argument and the call
    returns ``(out, state)`` instead.
    Differentiable; the backward pipeline is the transposed scan with
    reversed hops. This is the config-DSL pipeline path (trainer key
    ``pipeline_parallel``); `pipeline_apply` remains the fast path for
    uniform repeated-block stacks.
    """
    n_stages = mesh.shape[axis]
    if len(stage_fns) != n_stages:
        raise ValueError(
            "pipeline_apply_stages: %d stage fns for %d-way mesh axis %r"
            % (len(stage_fns), n_stages, axis))
    n_micro = x.shape[0]
    bspec = P(None, batch_spec, None) if batch_spec else P()
    pspec = params_spec if params_spec is not None else P()
    # Every mesh axis is MANUAL here, including a composed ``model`` axis:
    # stage bodies do tensor parallelism with explicit group-local
    # collectives (fullc all-gathers its column-parallel outputs over model
    # pairs at its own pipe rank). Leaving model automatic instead is a
    # DEADLOCK: Shardy would insert 8-participant resharding collectives
    # inside the rank-divergent lax.switch branches, and devices at other
    # pipe ranks never arrive at them. Manual model collectives lower with
    # replica groups that never span pipe ranks, so divergence is safe.
    if state0 is None:
        fn = shard_map(
            functools.partial(_pipeline_local_switch, axis_name=axis,
                              n_micro=n_micro, stage_fns=tuple(stage_fns)),
            mesh=mesh, in_specs=(pspec, bspec), out_specs=bspec)
        return fn(params, x)
    fn = shard_map(
        functools.partial(_pipeline_local_switch, axis_name=axis,
                          n_micro=n_micro, stage_fns=tuple(stage_fns),
                          state_masks=state_masks, data_axis=batch_spec),
        mesh=mesh, in_specs=(pspec, bspec, P()),
        out_specs=(bspec, P()))
    return fn(params, x, state0)


def pipeline_apply(stage_fn, stacked_params, x, mesh: Mesh, *,
                   axis: str = "pipe"):
    """Run microbatches through a pipeline of stages.

    stage_fn(params_s, act) -> act     one stage's forward
    stacked_params: pytree whose leaves have leading dim n_stages (sharded
                    or shardable on ``axis``)
    x: (n_micro, microbatch, ...) input microbatches

    Returns (n_micro, microbatch, ...) outputs, replicated. Differentiable —
    the backward pipeline runs as the transposed scan with reversed hops.
    """
    n_stages = mesh.shape[axis]
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                "pipeline_apply: stacked params leading dim %d != %d stages "
                "on mesh axis %r" % (leaf.shape[0], n_stages, axis))
    n_micro = x.shape[0]
    fn = shard_map(
        functools.partial(_pipeline_local, axis_name=axis, n_micro=n_micro,
                          stage_fn=stage_fn),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P())
    return fn(stacked_params, x)
