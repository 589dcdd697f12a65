"""Initial weights from the seed, made by the benchmark in one jitted call.

The program and the plain reference are both handed these values, so neither
takes anything the other has made. Shapes come from ``jax.eval_shape`` of the
program's own ``init``; values come from here: a leaf of rank >= 2 is a
kernel, N(0, scale^2 / fan_in) with fan_in its second-to-last axis; a leaf of
rank 1 is a bias, N(0, bias_std^2), not zero, so that no term drops out of
the comparison. ``overrides`` (substring of the leaf's path -> factor) keeps
layers that the program initialises tiny (EGNN's coordinate gate) tiny.
``unit_mean`` (substrings of leaf paths) names the rank-1 leaves that
multiply their input, a norm's ``scale``: those are 1 + N(0, std^2), since
noise round zero would set the layer's output to nothing. A rule without the
key makes the values it always made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds exceed
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31)), seed // (2**31))


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)


def make_weights(shapes, seed: int, rule: dict):
    """A pytree shaped like ``shapes`` (leaves with .shape/.dtype)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    scale = float(rule.get("kernel_scale", 1.0))
    bias_std = float(rule.get("bias_std", 0.01))
    overrides = dict(rule.get("overrides", {}))
    unit_mean = tuple(rule.get("unit_mean", ()))

    def factor(name: str) -> float:
        for sub, f in overrides.items():
            if sub in name:
                return float(f)
        return 1.0

    @jax.jit
    def make(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            name = path_name(path)
            if len(leaf.shape) >= 2:
                std = scale * factor(name) / float(leaf.shape[-2]) ** 0.5
            else:
                std = bias_std * factor(name)
            value = std * jax.random.normal(k, leaf.shape, jnp.float32)
            if len(leaf.shape) == 1 and any(sub in name for sub in unit_mean):
                value = 1.0 + value
            out.append(value)
        return out

    return jax.tree_util.tree_unflatten(treedef, make(seed_key(seed)))


def flat_dict(tree) -> dict:
    """{"a/b/kernel": array} — how the reference is handed its weights."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_name(p): v for p, v in flat}
