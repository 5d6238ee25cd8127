"""Builds the system under test from the benchmark's weights."""
from __future__ import annotations

import jax.numpy as jnp

from . import weights as W


def llama_config(model: dict, max_positions: int):
    from paddle_tpu.models.nlp import LlamaConfig
    if model["hidden_size"] != model["num_attention_heads"] * model["head_dim"]:
        raise ValueError("the program derives head_dim as hidden/heads")
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        tie_word_embeddings=model["tie_word_embeddings"],
        sliding_window=model.get("sliding_window"), dtype=jnp.bfloat16)


def empty_model(model: dict, max_positions: int):
    """The program's model object with one-element placeholders for weights:
    its own float32 initial values (4 bytes a parameter, made leaf by leaf)
    are dropped at once."""
    from paddle_tpu.models.nlp import LlamaForCausalLM
    net = LlamaForCausalLM(llama_config(model, max_positions))
    drop_weights(net)
    net.eval()
    net.to(dtype="bfloat16")
    return net


def drop_weights(net):
    net.load_tree({k: jnp.zeros((1,), jnp.bfloat16) for k in net.state_dict()})


def load_weights(net, model: dict, seed: int, shardings=None):
    net.load_tree(W.make_weights(model, seed, shardings))
