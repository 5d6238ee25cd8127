"""Decides ``correct`` for a training cell.

The program's first three steps (taken in set-up through the window's own
call and feed) are followed by the plain reference once the window has
closed and the program's state is freed.  Compared: each step's loss; the
first gradient as the optimizer got it, worked out from the first moment
after one step (m1 = (1 - beta1) g); the parameters' change after the three
steps.  Norms are compared by the worst leaf: the gap between the
program's norm and the reference's against the reference's norm of that
leaf or of the median leaf, whichever is larger.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

NEGLIGIBLE_GRAD = 1e-3     # of the median leaf's: such a leaf moves by round-off alone


def _leaf_norms(family, tree):
    return {k: family.R.leaf_norm(v) for k, v in tree.items()}


@partial(jax.jit, static_argnums=(0, 1))
def _change_norms_of(family, model_items, key, params):
    model = dict(model_items)
    return {k: family.R.leaf_norm(p.astype(jnp.float32)
                                  - W.initial_leaf(family, model, key, k).astype(jnp.float32))
            for k, p in params.items()}


def _change_norms(family, model, seed):
    """Per-leaf norm of (params - their initial values), the initial values
    drawn again from the seed inside the program, leaf by leaf.  The key is
    an argument, not a constant: one compiled program serves every seed."""
    items = tuple(sorted((k, v) for k, v in model.items()))
    return lambda params: _change_norms_of(family, items, W.key_of(seed, 1), params)


def _host(tree) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def first_steps(trainer, steps: int, keep_first_moment: bool, marks=None) -> dict:
    """Drive the trainer's first ``steps`` steps; bring back small numbers
    only, and the first moment after step one (to the host, so that nothing
    of the comparison stays on the device through the window)."""
    beta1 = trainer.job["optimizer"]["beta1"]
    mark = (lambda name: None) if marks is None else marks.add
    losses = [float(trainer.advance())]
    mark("first_step")
    gnorm = {k: v / (1 - beta1) for k, v in
             _host(jax.jit(partial(_leaf_norms, trainer.family))(trainer.opt["m"])).items()}
    m1 = jax.device_get(trainer.opt["m"]) if keep_first_moment else None
    mark("first_moment_to_host")
    for _ in range(steps - 1):
        losses.append(float(trainer.advance()))
    change = _host(_change_norms(trainer.family, trainer.model, trainer.seed)(trainer.params))
    mark("steps_2_3_and_change")
    return {"losses": losses, "gnorm": gnorm, "change": change, "m1_host": m1}


def reference_shardings(family, model: dict, mesh):
    """The reference's leaves spread over every chip the cell has (first
    dimension), so that its float32 gradients fit beside its state."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.size
    axes = tuple(mesh.axis_names)
    out = {}
    for name, shape in family.leaf_shapes(model).items():
        spec = P(axes) if n > 1 and shape[0] % n == 0 else P()
        out[name] = NamedSharding(mesh, spec)
    return out


def reference_first_steps(family, model, job, seed, mesh, quant=None, m1_other=None,
                          keep_first_moment=False, steps=3) -> dict:
    """The reference's first steps (or, with ``quant``, the control's):
    the same record as ``first_steps`` gives for the program, and with
    ``m1_other`` the per-leaf norms of (the other side's first gradient
    minus this one's)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    R, hp = family.R, job["optimizer"]
    sh = reference_shardings(family, model, mesh)
    spread_batch = mesh.size > 1 and job["batch"] % mesh.size == 0
    rep = NamedSharding(mesh, P(tuple(mesh.axis_names)) if spread_batch else P())
    params = W.make_weights(family, model, seed, sh)
    zeros = jax.jit(lambda: {k: jnp.zeros(s, jnp.dtype(job["moments_dtype"]))
                             for k, s in family.leaf_shapes(model).items()},
                    out_shardings=sh)
    tokens, labels = W.make_batches(seed, job["batch"], job["seq"],
                                    model["vocab_size"], rep)
    grads_of = jax.jit(partial(R.loss_and_grads, model, quant, not spread_batch),
                       out_shardings=(NamedSharding(mesh, P()), sh))
    update = jax.jit(partial(R.adamw, hp=hp), donate_argnums=(0, 2, 3))
    norm, diff_norm = jax.jit(R.leaf_norm), jax.jit(R.leaf_diff_norm)
    scale = 1.0 / (1 - hp["beta1"])
    losses, gnorm, diff, m1 = [], None, None, None
    m = v = None
    for i in range(steps):
        loss, grads = grads_of(params, tokens[i], labels[i])
        losses.append(float(loss))
        if i == 0:
            gnorm = _host({k: norm(g) for k, g in grads.items()})
            if m1_other is not None:    # leaf by leaf, so that both sides fit
                diff = _host({k: diff_norm(jax.device_put(m1_other[k], sh[k]), scale, g)
                              for k, g in grads.items()})
            m, v = zeros(), zeros()     # only now: the gradients had the room
        for k in list(params):
            params[k], m[k], v[k] = update(params[k], grads.pop(k), m[k], v[k],
                                           jnp.float32(i + 1))
        if i == 0 and keep_first_moment:
            m1 = jax.device_get(m)
    change = _host(_change_norms(family, model, seed)(params))
    return {"losses": losses, "gnorm": gnorm, "change": change, "diff": diff,
            "m1_host": m1}


def compare(family, model, job, seed, first: dict, mesh):
    """The numbers compared for ``correct`` and what else the line prints."""
    ref = reference_first_steps(family, model, job, seed, mesh, None, first.get("m1_host"),
                                steps=len(first["losses"]))
    numbers = {}
    for i, (a, b) in enumerate(zip(first["losses"], ref["losses"]), 1):
        numbers[f"loss{i}_rel_gap"] = abs(a - b) / abs(b)
    g_med = float(np.median(list(ref["gnorm"].values())))
    c_med = float(np.median(list(ref["change"].values())))
    worst = {}

    def worst_leaf(label, gaps):
        name = max(gaps, key=gaps.get)
        worst[label] = name
        return gaps[name]

    numbers["grad_norm_gap_worst_leaf"] = worst_leaf("grad", {
        k: abs(first["gnorm"][k] - r) / max(r, g_med) for k, r in ref["gnorm"].items()})
    left_out = sorted(k for k, r in ref["gnorm"].items() if r < NEGLIGIBLE_GRAD * g_med)
    numbers["change_norm_gap_worst_leaf"] = worst_leaf("change", {
        k: abs(first["change"][k] - r) / max(r, c_med)
        for k, r in ref["change"].items() if k not in left_out})
    if ref["diff"] is not None:
        numbers["grad_diff_worst_leaf"] = worst_leaf("diff", {
            k: d / max(ref["gnorm"][k], g_med) for k, d in ref["diff"].items()})
    return numbers, {"ref_losses": ref["losses"], "worst_leaves": worst,
                     "left_out_of_change": left_out}
