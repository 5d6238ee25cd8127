"""Operations and bytes a call needs, from its shapes alone.

Independent of what implements the call: a matmul of (m, k) by (k, n) is
2·m·k·n operations, attention over a context of c keys is 4·c·heads·dim per
query token (scores and weighted sum), halved by the causal mask when a
whole sequence attends to itself.  Recomputed work never counts.

Here is the arithmetic of attention and of its kernels, which every
family shares; what a whole pass of a model costs is its family's to count
(``benchmark/families/<family>.py``), and may call this.
"""
from __future__ import annotations


def attention_flops_per_token(cfg: dict, context: float) -> float:
    """Forward attention operations of one query token over ``context`` keys,
    all layers."""
    return (4.0 * context * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])


def flash_flops(batch: int, seq: int, heads: int, dim: int,
                backward: bool) -> float:
    """Causal self-attention kernel over whole sequences: forward is two
    matmuls of (seq, dim) by (dim, seq) per head over the causal half;
    backward is five such."""
    fwd = 4.0 * batch * heads * seq * seq * dim / 2.0
    return fwd * (2.5 if backward else 1.0)


def paged_decode_bytes(cfg: dict, context_tokens: float, kv_bytes: int = 2) -> float:
    """Bytes of keys and values one decode step must read for rows whose
    contexts add up to ``context_tokens``, all layers."""
    return (2.0 * context_tokens * cfg["num_key_value_heads"] * cfg["head_dim"]
            * kv_bytes * cfg["num_hidden_layers"])


def paged_decode_flops(cfg: dict, context_tokens: float) -> float:
    return attention_flops_per_token(cfg, context_tokens)


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take for that work."""
    return max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])
