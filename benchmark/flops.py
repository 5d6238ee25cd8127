"""Operations and bytes a call needs, from its shapes alone.

Independent of what implements the call: a matmul of (m, k) by (k, n) is
2·m·k·n operations, attention over a context of c keys is 4·c·heads·dim per
query token (scores and weighted sum), halved by the causal mask when a
whole sequence attends to itself.  Recomputed work never counts.
"""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    H, I = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return H * q + 2 * H * kv + q * H + 3 * H * I


def matmul_params(cfg: dict) -> int:
    """Weights that every token is multiplied by: the layers and the head
    (the embedding is a lookup)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops_per_token(cfg: dict, context: float) -> float:
    """Forward attention operations of one query token over ``context`` keys,
    all layers."""
    return (4.0 * context * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])


def forward_flops(cfg: dict, new_tokens: int, context_start: int,
                  head_tokens: int | None = None) -> float:
    """Forward pass of ``new_tokens`` tokens that follow ``context_start``
    cached ones; the head runs on ``head_tokens`` of them (all by default)."""
    head_tokens = new_tokens if head_tokens is None else head_tokens
    body = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * new_tokens
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    # token i (0-based) attends to context_start + i + 1 keys
    ctx_sum = new_tokens * context_start + new_tokens * (new_tokens + 1) / 2.0
    return body + head + attention_flops_per_token(cfg, 1.0) * ctx_sum


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward pass."""
    return 3.0 * batch * forward_flops(cfg, seq, 0)


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
