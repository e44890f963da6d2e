"""Seeded weights of a dense decoder configuration, made on the device in
one jitted call, in the type they are served in.

The tree has the layout the program's model takes (``embed.tok``, layers
stacked on a leading axis, ``final_norm``, and ``head.w`` unless the head
is tied to the embedding, when ``head`` is empty); a test checks it
against the program's own initializer by shape.  The reference builds the
same weights from the same seed, so it takes nothing the program made.
"""
from __future__ import annotations

import functools
import math


def padded_vocab(config: dict) -> int:
    return 256 * math.ceil(int(config["vocab_size"]) / 256)


def leaf_specs(config: dict) -> dict:
    """name path -> (shape, init): ``("normal", std)`` or ``("ones",)``."""
    L, d = int(config["n_layers"]), int(config["d_model"])
    H, K = int(config["n_heads"]), int(config["n_kv_heads"])
    hd, ff, V = int(config["head_dim"]), int(config["d_ff"]), padded_vocab(config)

    def dense(fan_in, fan_out, stacked=True):
        shape = (L, fan_in, fan_out) if stacked else (fan_in, fan_out)
        return shape, ("normal", 1.0 / math.sqrt(fan_in))

    specs = {
        ("embed", "tok"): ((V, d), ("normal", 0.02)),
        ("layers", "ln1", "scale"): ((L, d), ("ones",)),
        ("layers", "attn", "wq"): dense(d, H * hd),
        ("layers", "attn", "wk"): dense(d, K * hd),
        ("layers", "attn", "wv"): dense(d, K * hd),
        ("layers", "attn", "wo"): dense(H * hd, d),
        ("layers", "ln2", "scale"): ((L, d), ("ones",)),
        ("layers", "mlp", "w_gate"): dense(d, ff),
        ("layers", "mlp", "w_up"): dense(d, ff),
        ("layers", "mlp", "w_down"): dense(ff, d),
        ("final_norm", "scale"): ((d,), ("ones",)),
    }
    if not config.get("tie_embeddings"):
        specs[("head", "w")] = dense(d, V, stacked=False)
    return specs


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
        for k, (path, (shape, init)) in zip(keys, spec_items):
            if init[0] == "ones":
                flat[path] = jnp.ones(shape, dtype)
            else:
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * init[1]).astype(dtype)
        return dict({"head": {}}, **_nest(flat))

    return jax.jit(build)


def make(config: dict, seed: int):
    """The configuration's weights for ``seed``, on the default device."""
    items = tuple(sorted(leaf_specs(config).items()))
    return _builder(items, str(config["param_dtype"]))(seed_key(seed))
