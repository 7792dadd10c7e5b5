"""The reference's first steps: seeded weights, loss and gradients through a
plain reference, the optimizer's update through optax as the job states it.

``follow`` returns what the comparison reads: each step's loss, the optimizer
state's view of the first gradient, and the change of every leaf after the
steps.  ``mode="fp8"`` is the control; ``fault="half_batch"`` plants the
fault of a batch half left out (rows, or positions where there is one row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from benchmark import compare, families, weights
from benchmark.reference.numerics import Numerics


def make_tx(job) -> optax.GradientTransformation:
    if job["optimizer"] == "sgd_nesterov":
        return optax.chain(
            optax.add_decayed_weights(job["weight_decay"]),
            optax.sgd(optax.warmup_cosine_decay_schedule(
                0.0, job["lr"], job["warmup_steps"], job["total_steps"]),
                momentum=job["momentum"], nesterov=True))
    if job["optimizer"] == "adafactor":
        return optax.adafactor(job["lr"])
    raise ValueError(f"unknown optimizer {job['optimizer']!r}")


def _halve(batch):
    def cut(x):
        if x.shape[0] >= 2:
            return x[: x.shape[0] // 2]
        return x[:, : x.shape[1] // 2]
    return jax.tree.map(cut, batch)


def programs(config, *, mode: str = "f32", fault: str | None = None):
    """(init, step, spec): the reference's two jitted programs."""
    mod = families.load(config["family"]).reference
    model, job = config["model"], config["job"]
    spec = mod.param_spec(model)
    tx = make_tx(job)
    num = Numerics(mode)

    @jax.jit
    def init(key):
        params = weights.make(spec, key)
        return params, tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, batch):
        if fault == "half_batch":
            batch = _halve(batch)
        loss, grads = jax.value_and_grad(
            lambda p: mod.loss(model, job, p, batch, num))(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    return init, step, spec


def follow(config, seed: int, batches, *, mode: str = "f32",
           fault: str | None = None) -> dict:
    init, step, spec = programs(config, mode=mode, fault=fault)
    key = weights.seed_key(seed)
    params, opt = init(key)
    out = {"loss": []}
    for i, batch in enumerate(batches):
        params, opt, loss = step(params, opt, jax.tree.map(jnp.asarray, batch))
        out["loss"].append(float(loss))
        if i == 0:
            out["grad_norm"] = compare.fetch(compare.first_grad_norms(opt, params))
    out["delta_norm"] = compare.fetch(compare.delta_norms(params, spec, key))
    del params, opt
    return out
