"""shard_map with the vma check off, and one jax workaround.

Collective-heavy bodies (ring scans, pipelines) mix axis-varying and
invariant carries, so ``shard_map`` here always disables the vma check.

The workaround (still needed on jax 0.9.0 — without it a pipelined step
with dropout in one stage only fails the typematch assertion):
differentiating lax.switch whose branches sample PRNG noise asymmetrically
(a dropout stage next to a dropout-free stage in the GPipe pipeline) pads
the missing typed-key residual with ``zeros_like_aval``, which returns
float0 for key avals and trips the cond partial-eval typematch invariant
(jax/_src/lax/control_flow/conditionals.py). We teach zeros_like_aval to
produce a zero KEY instead — the padded residual is dead in the branches
that receive it, so any well-typed placeholder is correct. The patch is
applied lazily (first pipelined forward), not at import, so processes that
never differentiate a pipeline keep stock jax behavior."""

from __future__ import annotations

import jax


def _patch_key_zeros() -> None:
    import jax.numpy as jnp
    from jax._src import ad_util

    if getattr(ad_util, "_cxxnet_key_zeros_patch", False):
        return
    orig = ad_util.zeros_like_aval

    def zeros_like_aval(aval):
        dt = getattr(aval, "dtype", None)
        if dt is not None and jax.dtypes.issubdtype(
                dt, jax.dtypes.prng_key):
            impl = dt._impl
            kd = jnp.zeros(tuple(aval.shape) + tuple(impl.key_shape),
                           jnp.uint32)
            return jax.random.wrap_key_data(kd, impl=impl.name)
        return orig(aval)

    ad_util.zeros_like_aval = zeros_like_aval
    ad_util._cxxnet_key_zeros_patch = True


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
