"""A drop-free sparse feed-forward with shared experts, told which
experts it holds.

Routing is over all ``E`` experts whatever is held: ``s = sigmoid_f32(x .
W_g)``, the ``k`` experts with the largest ``s + b`` are chosen (``b`` the
per-expert selection bias of ``topk_method: noaux_tc``, where the layer
has that leaf; one group, so no group limit), and their weights are
``s[chosen] / (sum s[chosen] + 1e-20) * scaling`` — the bias moves the
choice, never the weight.  The router's settings (``Router``: ``k``,
``E``, scaling, whether the chosen scores are normalised) come from
whichever config hands them (``router_of``): a config that has a
``router`` attribute gives its own, one with ``deepseek_v3``'s keys is
read by their names.  There is no capacity factor and no
token is dropped: the ``N x k`` (token, expert) pairs are sorted by
expert and the three matrix products run as grouped products over the
sorted rows (``jax.lax.ragged_dot``, which XLA:TPU lowers to a grouped
matmul that reads only the experts that received a token).  The pairs'
outputs go back to token order and are combined by the router's weights
in float32.  The trainer's layer (``incubate/distributed/models/moe``)
dispatches into fixed-capacity buffers and drops what does not fit:
another result, not used here.

``held`` names the experts whose matrices the stacks carry, in the
stacks' order (all ``E`` on one chip that holds the layer whole; a
chip's share of an expert-parallel deployment is the same call with
fewer ids and smaller stacks).  ``routed_part`` computes exactly what the
held experts add to each token; what every chip computes alike, the
shared expert, is ``shared_part`` and is added once (``expert_layer``).

Leaves of one layer (``state_dict`` names under ``model.layers.<i>.``):
``mlp.gate.weight`` (H, E), ``mlp.gate.e_score_correction_bias`` (E,;
optional),
``mlp.experts.gate_proj`` / ``up_proj`` (held, H, I), ``down_proj``
(held, I, H), ``mlp.shared_experts.{gate,up}_proj.weight`` (H, S * I),
``mlp.shared_experts.down_proj.weight`` (S * I, H).  The expert stacks are
leaves of their own per layer and are handed to the grouped product
whole: a slice of an ``(L, E, H, I)`` stack would be copied first.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

ROUTER = "mlp.gate.weight"
ROUTER_BIAS = "mlp.gate.e_score_correction_bias"
EXPERT_KEYS = ("mlp.experts.gate_proj", "mlp.experts.up_proj",
               "mlp.experts.down_proj")
SHARED_KEYS = ("mlp.shared_experts.gate_proj.weight",
               "mlp.shared_experts.up_proj.weight",
               "mlp.shared_experts.down_proj.weight")
# what one call counts, in this order (``routed_part``'s second result)
ROUTE_COUNTS = ("pairs", "experts_hit", "max_expert_pairs")


@dataclasses.dataclass(frozen=True)
class Router:
    """One router's settings, whatever the config calls them."""
    k: int                      # experts a token
    n_experts: int              # E: the router's width
    scaling: float              # on the chosen, normalised scores
    normalise: bool = True      # chosen scores divided by their sum
    scoring: str = "sigmoid"
    groups: tuple = (1, 1)      # (n_group, topk_group)


def router_of(cfg) -> Router:
    """The router's settings from whichever config hands them: its own
    ``router`` (a ``Router``), else ``deepseek_v3``'s keys."""
    own = getattr(cfg, "router", None)
    if own is not None:
        return own
    if cfg.topk_method != "noaux_tc":
        raise NotImplementedError(
            "the router chooses the k largest sigmoid scores, a per-expert "
            "selection bias added where the layer has one "
            f"(topk_method='noaux_tc'); got {cfg.topk_method!r}")
    return Router(k=cfg.num_experts_per_tok, n_experts=cfg.n_routed_experts,
                  scaling=cfg.routed_scaling_factor,
                  normalise=cfg.norm_topk_prob, scoring=cfg.scoring_func,
                  groups=(cfg.n_group, cfg.topk_group))


def route(cfg, router_w, bias, x):
    """x (N, H) -> (weights (N, k) float32, experts (N, k) int32): the
    router in float32 whatever the activations' type.  ``bias`` (E,) moves
    the choice alone; None where the layer has no such leaf."""
    r = router_of(cfg)
    if r.scoring != "sigmoid" or r.groups != (1, 1):
        raise NotImplementedError(
            "the router computes sigmoid scores over one group of experts "
            "(the k largest, optionally biased, optionally normalised, "
            f"scaled); got scoring {r.scoring!r}, (n_group, topk_group) "
            f"{r.groups}")
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    chosen_by = s if bias is None else s + bias.astype(jnp.float32)[None, :]
    _, idx = jax.lax.top_k(chosen_by, r.k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if r.normalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * r.scaling, idx.astype(jnp.int32)


def routed_part(cfg, lp, x, held=None):
    """x (N, H) -> (y (N, H) float32, counts (3,) int32 as
    ``ROUTE_COUNTS``): what the experts ``held`` (ids in the stacks'
    order; None = all of them) add to each token, and the call's held
    (token, expert) pairs, held experts that received a token, and the
    largest held expert's pairs."""
    N, H = x.shape
    r = router_of(cfg)
    k, E = r.k, r.n_experts
    n_held = lp[EXPERT_KEYS[0]].shape[0]
    with jax.named_scope("moe.route"):
        w, idx = route(cfg, lp[ROUTER], lp.get(ROUTER_BIAS), x)
        if held is None:
            if n_held != E:
                raise ValueError(f"the stacks hold {n_held} of {E} "
                                 "experts: say which (held=)")
            local = idx
        else:
            held = np.asarray(held, np.int32)
            if held.shape != (n_held,):
                raise ValueError(f"held names {held.shape} experts, the "
                                 f"stacks hold {n_held}")
            # an expert held elsewhere sorts behind every group (the map is
            # made on the host: as a traced scatter its indices and updates
            # are one constant where held is 0..n-1, which XLA:TPU's
            # scatter fusion aborts on)
            place = np.full((E,), n_held, np.int32)
            place[held] = np.arange(n_held, dtype=np.int32)
            local = jnp.asarray(place)[idx]
        flat = local.reshape(-1)                     # pair p = token p // k
        order = jnp.argsort(flat, stable=True)       # pairs grouped by expert
        sizes = jnp.zeros((n_held + 1,), jnp.int32).at[flat].add(1)[:n_held]
        xs = jnp.take(x, order // k, axis=0)         # (N*k, H)
    with jax.named_scope("moe.experts"):
        gate = jax.lax.ragged_dot(xs, lp[EXPERT_KEYS[0]], sizes)
        up = jax.lax.ragged_dot(xs, lp[EXPERT_KEYS[1]], sizes)
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, lp[EXPERT_KEYS[2]],
                                 sizes)              # (N*k, H), sorted order
    with jax.named_scope("moe.combine"):
        back = jnp.argsort(order)                    # pair p's sorted row
        pairs = jnp.take(out, back, axis=0).astype(jnp.float32)
        pairs = pairs.reshape(N, k, H)
        if held is not None:     # rows past the last group are not results
            pairs = jnp.where((local < n_held)[..., None], pairs, 0.0)
        y = jnp.einsum("nk,nkh->nh", w, pairs)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0).astype(jnp.int32),
                        jnp.max(sizes)])
    return y, counts


def shared_part(lp, x):
    """The shared experts as one SwiGLU of their summed width, which every
    chip computes alike and a sum over chips counts once."""
    with jax.named_scope("moe.shared"):
        g, u, d = (lp[key] for key in SHARED_KEYS)
        return (jax.nn.silu(x @ g) * (x @ u)) @ d


def expert_layer(cfg, lp, h, held=None):
    """h (B, T, H) -> (y (B, T, H), counts (3,) int32): the held experts'
    part of the routed result plus the shared expert, once."""
    B, T, H = h.shape
    x = h.reshape(B * T, H)
    y, counts = routed_part(cfg, lp, x, held)
    y = y + shared_part(lp, x).astype(jnp.float32)
    return y.astype(h.dtype).reshape(B, T, H), counts
