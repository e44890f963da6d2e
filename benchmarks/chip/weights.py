"""Seeded weights of a configuration, made on the device in one jitted call,
in the type they are served in.

The layout is the configuration's reference module's: its ``leaf_specs``
maps each leaf's name path to ``(shape, init)`` or ``(shape, init,
dtype)``, where ``init`` is ``("normal", std)``, ``("ones",)`` or
``("zeros",)`` and a leaf that names no dtype takes the configuration's
``param_dtype``.  The tree has the layout the program's model takes, with
``head`` empty where no leaf lies under it (a tied head); a test checks
each configuration's against the program's own initializer by shape and
dtype.  The reference builds the same weights from the same seed, so it
takes nothing the program made.
"""
from __future__ import annotations

import functools

from benchmarks.chip import harness


def seed_key(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits included)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


@functools.lru_cache(maxsize=None)
def _builder(spec_items: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def build(key):
        keys = jax.random.split(key, len(spec_items))
        flat = {}
        for k, (path, spec) in zip(keys, spec_items):
            shape, init = spec[:2]
            dt = spec[2] if len(spec) > 2 else dtype
            if init[0] == "ones":
                flat[path] = jnp.ones(shape, dt)
            elif init[0] == "zeros":
                flat[path] = jnp.zeros(shape, dt)
            elif init[0] == "normal":
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * init[1]).astype(dt)
            else:
                raise ValueError(f"leaf {path}: no init {init[0]!r}")
        return dict({"head": {}}, **_nest(flat))

    return jax.jit(build)


def make(config: dict, seed: int):
    """The configuration's weights for ``seed``, on the default device: one
    key a leaf, split in the order of the sorted name paths."""
    specs = harness.reference(config).leaf_specs(config)
    items = tuple(sorted(specs.items()))
    return _builder(items, str(config["param_dtype"]))(seed_key(seed))
