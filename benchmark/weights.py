"""Seeded weights, made by the benchmark and handed to program and reference alike.

A reference module states its parameter tree as a flat mapping
``"a/b/c" -> (shape, mean, std)``; :func:`make` draws every leaf on the device
from one key (folded with the leaf's path), in float32, inside whatever jit
calls it.  The program receives the result through its ``init_fn``; the
reference calls the same function with the key of the same ``--seed``, so
neither takes anything the other has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

Spec = dict[str, tuple[tuple[int, ...], float, float]]


def seed32(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; numpy's generators take 32."""
    return int(seed) % (2 ** 32)


def seed31(seed: int) -> int:
    """The seed as the program's ``--seed`` takes it."""
    return int(seed) % (2 ** 31)


def key_from_trainer(rng):
    """The benchmark's key from the one the program's ``Trainer`` hands its
    ``init_fn``.  The key is data, not a constant of the program, so one
    compiled program serves every seed.  It is carried over to XLA's own bit
    generator: one operation a leaf, where threefry unrolls into hundreds and
    the 161 leaves of a ResNet-50 take seconds to compile."""
    data = jax.random.key_data(rng).astype(jnp.uint32).reshape(-1)
    return jax.random.wrap_key_data(jnp.concatenate([data, data])[:4],
                                    impl="rbg")


def seed_key(seed: int):
    """The same key from ``--seed`` alone, for the reference: the loop makes
    ``jax.random.key(seed)`` and ``Trainer._create_state`` hands ``init_fn``
    the first half of its split."""
    return key_from_trainer(jax.random.split(jax.random.key(seed31(seed)))[0])


def _leaf(key, path: str, shape, mean: float, std: float):
    if std == 0.0:
        return jnp.full(shape, mean, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return mean + std * jax.random.normal(k, shape, jnp.float32)


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def make(spec: Spec, key) -> dict:
    """The nested float32 tree for ``spec`` under ``key`` (traceable)."""
    return unflatten({p: _leaf(key, p, tuple(s), m, sd)
                      for p, (s, m, sd) in spec.items()})


def make_leaf(spec: Spec, key, path: str):
    s, m, sd = spec[path]
    return _leaf(key, path, tuple(s), m, sd)
