"""Weights and training batches from ``--seed``, made on the device.

The benchmark's own generator: the program is loaded from it and the plain
reference draws the same values from it again, so neither takes anything
the other has made.  One jitted call makes every leaf, in bfloat16, the
type the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02          # Mistral's initializer_range


def key_of(seed: int, stream: int = 0):
    """A key from any whole seed up to and past 2**31."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def leaf_shapes(cfg: dict) -> dict:
    """name -> shape, in the names the program's state_dict uses
    (linear weights are stored (in, out))."""
    H, I, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"model.embed_tokens.weight": (V, H)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        shapes[p + "input_layernorm.weight"] = (H,)
        shapes[p + "self_attn.q_proj.weight"] = (H, q)
        shapes[p + "self_attn.k_proj.weight"] = (H, kv)
        shapes[p + "self_attn.v_proj.weight"] = (H, kv)
        shapes[p + "self_attn.o_proj.weight"] = (q, H)
        shapes[p + "post_attention_layernorm.weight"] = (H,)
        shapes[p + "mlp.gate_proj.weight"] = (H, I)
        shapes[p + "mlp.up_proj.weight"] = (H, I)
        shapes[p + "mlp.down_proj.weight"] = (I, H)
    shapes["model.norm.weight"] = (H,)
    shapes["lm_head.weight"] = (H, V)
    return shapes


def _leaf(key, index: int, shape) -> jax.Array:
    k = jax.random.fold_in(key, index)
    if len(shape) == 1:      # norm gains near one, so that they matter
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    return (INIT_STD * jax.random.normal(k, shape, jnp.float32)
            ).astype(jnp.bfloat16)


def make_weights(cfg: dict, seed: int, shardings: dict | None = None,
                 only: list[str] | None = None) -> dict:
    """Every leaf (or the leaves ``only`` names) in one jitted call."""
    shapes = leaf_shapes(cfg)
    index = {name: i for i, name in enumerate(shapes)}
    names = list(shapes) if only is None else list(only)

    def build(key):
        return {n: _leaf(key, index[n], shapes[n]) for n in names}

    out_sh = None if shardings is None else {n: shardings[n] for n in names}
    return jax.jit(build, out_shardings=out_sh)(key_of(seed, 1))


def initial_leaf(cfg: dict, key, name: str) -> jax.Array:
    """One leaf's initial value, for use inside another jitted function."""
    shapes = leaf_shapes(cfg)
    return _leaf(key, list(shapes).index(name), shapes[name])


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
