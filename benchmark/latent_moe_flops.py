"""Operations and bytes of what a latent-attention expert model adds, from
shapes and routing alone: the same count whatever implements the call.

*The expert products.*  A (token, expert) pair costs three products against
one expert's matrices, ``gate`` and ``up`` (hidden x width) and ``down``
(width x hidden): ``6 * hidden * width`` operations.  An expert that
received a token has to be read once, whole: ``3 * hidden * width``
weights.  An expert that received none need not be read at all.

*Latent attention.*  As published (expanded), a query head scores a key over
``nope + rope`` values and sums over ``v``: ``2 * heads * (nope + rope + v)``
operations a (query, key) pair and layer — the model's operations, which
``mfu`` counts whatever path computes them.  The kernel runs the *absorbed*
form: a head scores a cached position over ``rank + rope`` values and sums
over its ``rank``, ``2 * heads * (2 * rank + rope)`` a pair — the kernel's
own work, which its roofline counts.  A decode step has to read each
attended position's ``rank + rope`` values once a layer, whatever the head
count.
"""
from __future__ import annotations


def expert_products_flops(cfg: dict, pairs: float) -> float:
    """Operations of the three products for ``pairs`` token-expert pairs."""
    return 6.0 * pairs * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_products_bytes(cfg: dict, experts_hit: float, weight_bytes: int = 2) -> float:
    """Bytes of the matrices of ``experts_hit`` experts (summed over the
    layers and calls that read them), each read once."""
    return (3.0 * experts_hit * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * weight_bytes)


def latent_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def absorbed_pair_flops(cfg: dict) -> float:
    """One (query, key) pair in one layer, all heads, absorbed."""
    return 2.0 * cfg["num_attention_heads"] * (latent_width(cfg) + cfg["kv_lora_rank"])


def expanded_pair_flops(cfg: dict) -> float:
    """One (query, key) pair in one layer, all heads, as published (on
    expanded K and V)."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def latent_decode_bytes(cfg: dict, cached_tokens_read: float, kv_bytes: int = 2) -> float:
    """Bytes of latent cache the decode rows' attention has to read, all
    layers: ``cached_tokens_read`` is the rows' lengths summed over steps."""
    return cached_tokens_read * latent_width(cfg) * kv_bytes * cfg["num_hidden_layers"]


def latent_decode_flops(cfg: dict, cached_tokens_read: float) -> float:
    return cached_tokens_read * absorbed_pair_flops(cfg) * cfg["num_hidden_layers"]
