"""Global-norm gradient clipping."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.runtime import scopes


def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                        for l in leaves))


def clip_by_global_norm(grads, max_norm: float):
    with jax.named_scope(scopes.OPTIMIZER):
        norm = global_norm(grads)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
        return jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
            grads), norm
