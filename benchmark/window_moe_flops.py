"""Operations and bytes of what a window-and-global expert model adds, from
shapes and the programs' own counts alone.

*Attention over two kinds of layer.*  A query head scores a key over
``head_dim`` values and sums over as many: ``4 * head_dim`` operations a
(query head, key) pair.  A full layer's token at position ``t`` has ``t +
1`` pairs a head, a sliding layer's ``min(t + 1, sliding_window)``: the
pairs beyond the window are not the model's work and are never counted.
A decode row has to read each position it may see once a layer, ``2 * n_kv
* head_dim`` values (K and V) whatever the number of query heads.  The
program counts those positions itself, by kind, summed over rows and over
the layers of the kind (``kv_tokens_read_global``, ``kv_tokens_read_window``):
``flops.paged_decode_bytes`` would read every layer to the row's end.

*The expert products*: ``benchmark/latent_moe_flops.py`` (one count for
every expert layer: ``hidden_size`` and ``moe_intermediate_size`` are the
keys here too).
"""
from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def heads_by_kind(cfg: dict) -> dict:
    """kind -> its layers' query heads summed (48 a full layer, 64 a
    sliding one)."""
    out = {FULL: 0, SLIDING: 0}
    for kind, n in zip(layer_kinds(cfg), cfg["num_attention_heads_per_layer"]):
        out[kind] += n
    return out


def pairs(cfg: dict, new_tokens: int, context_start: int) -> dict:
    """(query, key) pairs a head of ``new_tokens`` tokens after
    ``context_start`` cached ones, in a layer of each kind."""
    n, c, w = new_tokens, context_start, cfg["sliding_window"]
    full = n * c + n * (n + 1) / 2.0
    # token i (0-based) sees min(c + i + 1, w) keys
    below = min(max(w - c - 1, 0), n)               # tokens still under the window
    sliding = below * c + below * (below + 1) / 2.0 + (n - below) * w
    return {FULL: full, SLIDING: sliding}


def attention_flops(cfg: dict, new_tokens: int, context_start: int) -> float:
    """Scores and weighted sums of ``new_tokens`` tokens, all layers."""
    by_kind, heads = pairs(cfg, new_tokens, context_start), heads_by_kind(cfg)
    return sum(4.0 * cfg["head_dim"] * heads[k] * by_kind[k] for k in by_kind)


def kv_read_bytes(cfg: dict, tokens_global: float, tokens_window: float,
                  kv_bytes: int = 2) -> float:
    """Bytes of K and V behind the positions the program counted (already
    summed over the layers of each kind)."""
    return (tokens_global + tokens_window) * 2.0 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * kv_bytes


def kv_read_flops(cfg: dict, tokens_global: float, tokens_window: float) -> float:
    """The decode rows' attention operations behind the same counts: a
    counted position is one key for every query head of its layer."""
    kinds = layer_kinds(cfg)
    heads = heads_by_kind(cfg)
    per = {k: heads[k] / max(kinds.count(k), 1) for k in heads}
    return 4.0 * cfg["head_dim"] * (tokens_global * per[FULL] + tokens_window * per[SLIDING])


def held_bytes(pages_global: float, pages_window: float, page_bytes: dict) -> float:
    return pages_global * page_bytes["global"] + pages_window * page_bytes["window"]
