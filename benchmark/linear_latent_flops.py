"""Operations and bytes of what a linear-attention (KDA) layer with a
per-sequence state adds beside latent attention, from shapes and counts
alone: the same count whatever implements the call.

*The KDA state.*  A head's state is ``d_k x d_v`` float32.  One position
decays it, takes ``S^T k`` out of it, adds the rank-one write and reads
``S^T q``: ``2 * 3 * d_k * d_v`` operations a head (the published count),
whatever form computes it.  A decode step has to read each row's state once
and write it once a KDA layer: ``2 * 4 * heads * d_k * d_v`` bytes a row and
layer.  The convolutions' taps beside it are a thirtieth of that and are
not the kernel's to move.

*Latent attention* over the model's MLA layers alone (``mla_layers``):
``latent_moe_flops`` counts a layer; a model whose layers are not all
latent multiplies by its own count.
"""
from __future__ import annotations

from benchmark import latent_moe_flops as L


def kda_layers(cfg: dict) -> int:
    lin = cfg["linear_attn_config"]
    return sum(i + 1 in lin["kda_layers"] for i in range(cfg["num_hidden_layers"]))


def mla_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - kda_layers(cfg)


def kda_state_values(cfg: dict) -> int:
    """float32 values of one row's state in one KDA layer."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] * lin["head_dim"]


def kda_token_flops(cfg: dict) -> float:
    """State update and read of one position in one KDA layer."""
    return 2.0 * 3.0 * kda_state_values(cfg)


def kda_step_bytes(cfg: dict, rows_stepped: float) -> float:
    """Bytes the decode steps' states move: ``rows_stepped`` is rows x KDA
    layers summed over steps; each state read once and written once."""
    return 2.0 * 4.0 * kda_state_values(cfg) * rows_stepped


def kda_step_flops(cfg: dict, rows_stepped: float) -> float:
    return kda_token_flops(cfg) * rows_stepped


def state_entry_bytes(cfg: dict, act_bytes: int = 2) -> int:
    """One sequence's entry over every KDA layer: the states and the three
    convolutions' last ``K - 1`` inputs."""
    lin = cfg["linear_attn_config"]
    taps = (lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"] * lin["head_dim"]
    return kda_layers(cfg) * (4 * kda_state_values(cfg) + act_bytes * taps)


def latent_decode_bytes(cfg: dict, latent_tokens_read: float, kv_bytes: int = 2) -> float:
    """``latent_tokens_read``: the rows' lengths summed over steps AND over
    the latent layers (the program's own count)."""
    return latent_tokens_read * L.latent_width(cfg) * kv_bytes


def latent_decode_flops(cfg: dict, latent_tokens_read: float) -> float:
    return latent_tokens_read * L.absorbed_pair_flops(cfg)
