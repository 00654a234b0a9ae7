"""The training batch of diffusion over blocks (block diffusion language
models, Arriola et al. 2025, arXiv:2503.09573; the SDAR recipe): a batch
of token rows becomes rows of twice the length, a noised copy before the
clean one, and the two label fields the weighted per-position loss reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def noise_batch(tokens, key, block_len: int, t_min: float, t_max: float,
                mask_id: int):
    """``tokens`` (rows, L) ids -> ``(data, label)`` as ``Trainer.update``
    takes them under ``models.sdar_moe_conf``: ``data`` (rows, 1, 1, 2 L),
    the noised copy x_t then the clean copy x_0; ``label`` (rows, 2 L),
    the field ``label`` = x_0 then the field ``loss_weight``. Each block
    of ``block_len`` positions draws its noise level t uniformly from
    [t_min, t_max] and each of its positions becomes ``mask_id`` with
    probability t, independently; a masked position weighs 1 / t in the
    loss (the linear schedule's weight) and an unmasked one nothing.
    Float32, the program's convention for ids and labels; ``key`` is a
    jax PRNG key."""
    rows, L = tokens.shape
    if L % block_len:
        raise ValueError("a row of %d tokens is no whole number of blocks "
                         "of %d" % (L, block_len))
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (rows, L // block_len), jnp.float32,
                           t_min, t_max)
    t = jnp.repeat(t, block_len, axis=1)
    masked = jax.random.uniform(k_m, (rows, L), jnp.float32) < t
    x0 = tokens.astype(jnp.float32)
    xt = jnp.where(masked, jnp.float32(mask_id), x0)
    weight = jnp.where(masked, 1.0 / t, 0.0)
    data = jnp.concatenate([xt, x0], axis=1).reshape(rows, 1, 1, 2 * L)
    return data, jnp.concatenate([x0, weight], axis=1)
