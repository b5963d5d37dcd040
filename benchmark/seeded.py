"""Everything the benchmark draws from ``--seed``: weights and inputs.

The benchmark makes the weights, not the program: the runner places them
into the program's parameter tree and the reference builds its own from
the same leaf specs, so neither takes anything the other has made.

A leaf spec is ``(name, shape, init)`` with ``init`` one of
``("normal", std)``, ``("ones",)``, ``("zeros",)``, ``("uniform", lo, hi)``.
Leaf ``i`` of a list of specs is drawn from ``fold_in(key(seed), i)``, so a
leaf can be made alone (the reference, leaf by leaf) or with all the others
in one jitted call (the runner) and reads the same.
"""

from __future__ import annotations

import numpy as np


def key(seed: int):
    """A PRNG key from any whole number up to 2**63 (``PRNGKey`` alone
    refuses one over 32 bits)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def leaf(k, index, shape, init):
    """Leaf ``index`` of a spec list, float32. ``index`` may be traced:
    one program then serves every leaf of one shape and init."""
    import jax
    import jax.numpy as jnp

    kind = init[0]
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    sub = jax.random.fold_in(k, index)
    if kind == "normal":
        return init[1] * jax.random.normal(sub, shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(sub, shape, jnp.float32, init[1], init[2])
    raise ValueError(f"unknown init {init!r}")


def leaves(k, specs) -> dict:
    """All leaves by name from ``k = key(seed)``. Call it under ``jax.jit``
    with the key as an argument: one program on the device, the same
    program for every seed."""
    return {name: leaf(k, i, shape, init)
            for i, (name, shape, init) in enumerate(specs)}


def rng(seed: int, *stream: int) -> np.random.Generator:
    """The host generator for inputs: one independent stream per
    ``(seed, *stream)``."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def lm_tokens(seed: int, rank: int, batch: int, rows: int, seq_len: int,
              vocab: int) -> np.ndarray:
    """Pool batch ``batch`` of rank ``rank``: (rows, seq_len) int32,
    uniform over the vocabulary. Every (rank, batch) is its own stream, so
    all rows differ."""
    return rng(seed, 1, rank, batch).integers(
        0, vocab, size=(rows, seq_len), dtype=np.int32)


def images(k, rank, batch, rows: int, size: int, classes: int):
    """Pool batch ``batch`` of rank ``rank`` from ``k = key(seed)``:
    (rows, size, size, 3) bfloat16 standard normal images and (rows,) int32
    labels, made on the device (call under ``jax.jit`` with the sizes
    static); every (rank, batch) is its own stream."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(k, 2), rank), batch)
    ki, kl = jax.random.split(k)
    return (jax.random.normal(ki, (rows, size, size, 3),
                              jnp.float32).astype(jnp.bfloat16),
            jax.random.randint(kl, (rows,), 0, classes, jnp.int32))
