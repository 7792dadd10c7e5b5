"""The comparison that decides ``correct``.

Three numbers hold the timed path's first steps to the reference's, each by
its worst case; ``LIMIT`` names live in the cell's file.

- ``loss_gap``: the widest relative gap of a step's loss.
- ``grad_gap``: by the worst leaf, the gap between the program's and the
  reference's norm of the first gradient as the optimizer got it (read from
  each side's optimizer state after one step), against the reference's norm
  of that leaf or of the median leaf, whichever is larger.
- ``delta_gap``: the same of the parameters' change after the steps.  Leaves
  whose reference gradient is under a thousandth of the median leaf's move by
  round-off alone and are left out.

A step that returns its state unchanged reads ``delta_gap`` 1.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

NUMBERS = ("loss_gap", "grad_gap", "delta_gap")


def _paths(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): leaf for path, leaf in flat}


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _find(opt_state, type_name: str):
    """The optax state of that type inside a chain's nested tuples."""
    is_it = lambda s: type(s).__name__ == type_name  # noqa: E731
    return next((s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=is_it)
                 if is_it(s)), None)


@jax.jit
def first_grad_norms(opt_state, params) -> dict:
    """Per leaf, the norm of the gradient the optimizer was handed at its
    first step, from what its state keeps of it: momentum's trace (which after
    one step is that gradient, weight decay added) or Adafactor's second
    moments (which after one step, at decay 0, are its squares or their row
    means)."""
    trace = _find(opt_state, "TraceState")
    if trace is not None:
        return {p: _norm(t) for p, t in _paths(trace.trace).items()}
    fac = _find(opt_state, "FactoredState")
    if fac is None:
        raise ValueError("optimizer state keeps nothing of the gradient")
    sizes = {p: x.size for p, x in _paths(params).items()}
    rows, full = _paths(fac.v_row), _paths(fac.v)
    out = {}
    for p, n in sizes.items():
        if rows[p].size > 1:   # factored: row means of g*g
            out[p] = jnp.sqrt(jnp.sum(rows[p]) * (n / rows[p].size))
        else:
            out[p] = jnp.sqrt(jnp.sum(full[p]))
    return out


def delta_norms(params, spec, key) -> dict:
    """Per leaf, the norm of its change from the seeded weights, which are
    drawn again here leaf by leaf rather than kept."""
    @jax.jit
    def run(params, key):
        return {p: _norm(x - weights.make_leaf(spec, key, p))
                for p, x in _paths(params).items()}
    return run(params, key)


def fetch(tree) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, from two ``follow``-shaped readings."""
    n = min(len(prog["loss"]), len(ref["loss"]))
    if n == 0:
        raise ValueError("no step to compare")
    out = {"loss_gap": max(abs(p - r) / abs(r) for p, r in
                           zip(prog["loss"][:n], ref["loss"][:n]))}
    g_ref = ref["grad_norm"]
    med_g = statistics.median(g_ref.values())
    out["grad_gap"] = _worst(prog["grad_norm"], g_ref, g_ref.keys())
    moved = [p for p, g in g_ref.items() if g >= 1e-3 * med_g]
    out["delta_gap"] = _worst(prog["delta_norm"], ref["delta_norm"], moved)
    return out


def leaf_gaps(prog: dict, ref: dict, leaves) -> dict:
    leaves = list(leaves)
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:4]}")
    med = statistics.median(ref[p] for p in leaves)
    return {p: abs(prog[p] - ref[p]) / max(ref[p], med) for p in leaves}


def _worst(prog: dict, ref: dict, leaves) -> float:
    worst = max(leaf_gaps(prog, ref, leaves).values())
    return worst if np.isfinite(worst) else float("inf")


def worst_leaves(prog: dict, ref: dict, n: int = 6) -> dict:
    """For a look at what a number was made of: the leaves that read worst,
    each with the program's and the reference's norm."""
    out = {}
    for key in ("grad_norm", "delta_norm"):
        gaps = leaf_gaps(prog[key], ref[key], ref[key].keys())
        top = sorted(gaps, key=gaps.get, reverse=True)[:n]
        out[key] = [[p, gaps[p], prog[key][p], ref[key][p]] for p in top]
        out[key + "_median"] = statistics.median(ref[key].values())
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for the result line, each number beside its limit."""
    table = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
