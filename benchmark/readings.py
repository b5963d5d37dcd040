"""What a training runner reads from its state for ``correct``, shared by
the runners: per-leaf norms of a rank-stacked tree, one value a rank."""

from __future__ import annotations

import numpy as np


def leaf_norms(tree):
    """Rank-stacked tree → the same tree of (ranks,) norms, on the device."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                   axis=tuple(range(1, a.ndim)))), t))(tree)


def change_norms(hvd, seeded, seed: int, specs, now: dict) -> dict:
    """{leaf: [a rank's norm of its change since the seed's weights]}, the
    seed's weights made again one leaf at a time (a second copy of them all
    does not fit beside a large model's state). ``now`` is the rank-stacked
    leaves by name."""
    import jax
    import jax.numpy as jnp

    leaf = jax.jit(seeded.leaf, static_argnums=(2, 3))
    gap = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(
        jnp.square(a - b), axis=tuple(range(1, a.ndim)))))
    key = seeded.key(seed)
    on_device = {name: gap(now[name],
                           hvd.replicate(leaf(key, i, shape, init)))
                 for i, (name, shape, init) in enumerate(specs)}
    return {name: np.asarray(v).tolist() for name, v in on_device.items()}
