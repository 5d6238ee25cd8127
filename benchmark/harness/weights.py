"""Weights and training batches from ``--seed``, made on the device.

The benchmark's own generator: the program is loaded from it and the plain
reference draws the same values from it again, so neither takes anything
the other has made.  One jitted call makes every leaf, in bfloat16, the
type the configurations state.  Which leaves there are, and which of them
are gains, is the configuration's family's to say.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02          # every matrix; a family's gains are drawn near one


def key_of(seed: int, stream: int = 0):
    """A key from any whole seed up to and past 2**31."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _leaf(key, index: int, shape, gain: bool) -> jax.Array:
    k = jax.random.fold_in(key, index)
    if gain:                 # gains near one, so that they matter
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    return (INIT_STD * jax.random.normal(k, shape, jnp.float32)
            ).astype(jnp.bfloat16)


def make_weights(family, cfg: dict, seed: int, shardings: dict | None = None,
                 only: list[str] | None = None) -> dict:
    """Every leaf the family lists (or the leaves ``only`` names) in one
    jitted call; a leaf's values depend on the seed and its place in the list."""
    shapes = family.leaf_shapes(cfg)
    index = {name: i for i, name in enumerate(shapes)}
    names = list(shapes) if only is None else list(only)

    def build(key):
        return {n: _leaf(key, index[n], shapes[n], family.is_gain(n, shapes[n]))
                for n in names}

    out_sh = None if shardings is None else {n: shardings[n] for n in names}
    return jax.jit(build, out_shardings=out_sh)(key_of(seed, 1))


def initial_leaf(family, cfg: dict, key, name: str) -> jax.Array:
    """One leaf's initial value, for use inside another jitted function."""
    shapes = family.leaf_shapes(cfg)
    return _leaf(key, list(shapes).index(name), shapes[name],
                 family.is_gain(name, shapes[name]))


N_BATCHES = 8


def make_batches(seed: int, batch: int, seq: int, vocab: int, sharding=None,
                 n: int = N_BATCHES):
    """``n`` packed batches as two lists of (B, S) arrays, tokens and their
    next-token labels, every row different, drawn on the device."""
    def build(key):
        ids = jax.random.randint(key, (n, batch, seq + 1), 0, vocab, jnp.int32)
        return ([ids[i, :, :-1] for i in range(n)],
                [ids[i, :, 1:] for i in range(n)])
    out_sh = None if sharding is None else ([sharding] * n, [sharding] * n)
    return jax.jit(build, out_shardings=out_sh)(key_of(seed, 2))
