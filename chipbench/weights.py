"""Weights made from ``--seed`` on the device, in one jitted call, in the
type they are served in.

The values come from the benchmark, not from the program's initializer:
the program gives only the shape of its parameter tree, and each leaf is
filled here by a rule on its name.  The plain reference reads the same
arrays, so the program and the reference compute with the same weights
and neither made them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number from 0 to 2**64 - 1 (seeds may
    pass 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _scale(name: str, shape) -> float:
    """Standard deviation of a leaf, by its name in the tree:
    matrices ``w`` (…, d_in, d_out) 1/sqrt(d_in); the embedding table 1;
    biases ``b`` 0.1; norm weights, which the program stores as the
    scale's difference from 1, 0.1."""
    if name == "w":
        return float(shape[-2]) ** -0.5
    if name == "table":
        return 1.0
    return 0.1


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make_params(shapes, seed: int, device) -> dict:
    """A parameter tree shaped like ``shapes`` (``jax.eval_shape`` of the
    program's initializer), filled from ``seed`` on ``device``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, sds) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, sds.shape, sds.dtype)
            s = _scale(_leaf_name(path), sds.shape)
            leaves.append((x * jnp.asarray(s, sds.dtype)).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    sharding = jax.sharding.SingleDeviceSharding(device)
    key = jax.device_put(seed_key(seed), sharding)
    return jax.jit(build, out_shardings=sharding)(key)
