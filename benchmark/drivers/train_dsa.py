"""A training cell whose model chooses each query's keys (DeepSeek sparse
attention): ``drivers/train.py``'s run, with the program's choice at the
steps compared handed to the reference and held against the reference's own.

Scores in bfloat16 move keys across a row's ``index_topk``-th place, so the
reference is held to the program's arithmetic on the program's choice: the
tap reads, before each step compared, the keys the program's full layers
choose at that step's weights (the family's ``selection``), and the
reference attends over them (``dsa_words`` in each batch it follows).  Before
each of those steps the reference makes its own choice at its own weights,
and ``select_mismatch`` counts, summed over the steps, the keys the two
choices differ on whose reference score lies more than the cell's
``select_margin`` row deviations from the row's ``index_topk``-th score;
``select_gap`` (read, not held) is the largest such distance of any key they
differ on.  :func:`select_control` reads the same two numbers for the
reference's own choice from fp8 operands, which the margin has to hold off.

``train.run`` is the same function; two of the names it reads are replaced
for the length of the run, the tap and the reference's ``follow``.  Where the
run did not go through both (``train.run`` no longer reads them where it
runs), it fails: the choice is never left out of the verdict.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, families, window, weights
from benchmark.drivers import train
from benchmark.reference import train as ref_train
from benchmark.reference.numerics import Numerics

SELECT = ("select_mismatch", "select_gap")
UNUSED = ("train.run no longer builds its tap from benchmark.window.StepTap or "
          "follows the reference through benchmark.reference.train.follow: "
          "the choice was not compared")


@contextlib.contextmanager
def _replaced(module, name: str, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def run(c: dict, a, devices, keep: dict | None = None) -> dict:
    config, check = c["config"], c["cell"]["check"]
    family = families.load(config["family"])
    choose = family.selection(config)
    chosen: list[np.ndarray] = []
    found: dict = {}

    class Tap(window.StepTap):
        def __call__(self, state, batch):
            if len(self.loss) < self.steps:
                chosen.append(np.asarray(choose(state.params, batch["tokens"])))
            return super().__call__(state, batch)

    def follow_checked(config, seed, batches):
        """``ref_train.follow`` on the program's choice, with the two choices
        compared before each step."""
        if len(chosen) != len(batches):
            raise RuntimeError(UNUSED)
        for batch, words in zip(batches, chosen):
            batch["dsa_words"] = words   # the kept batch: a control follows it too
        differ = family.reference.selection_check(config["model"],
                                                  check["select_margin"])
        init, step, spec = ref_train.programs(config)
        key = weights.seed_key(seed)
        params, opt = init(key)
        out, counts, gaps = {"loss": []}, [], []
        for i, batch in enumerate(batches):
            n, gap = differ(params, batch["tokens"], batch["dsa_words"])
            counts.append(n)
            gaps.append(gap)
            params, opt, loss = step(params, opt, jax.tree.map(jnp.asarray, batch))
            out["loss"].append(float(loss))
            if i == 0:
                out["grad_norm"] = compare.fetch(compare.first_grad_norms(opt, params))
        out["delta_norm"] = compare.fetch(compare.delta_norms(params, spec, key))
        found.update(select_mismatch=sum(counts), select_gap=max(gaps))
        return out

    with (_replaced(window, "StepTap", Tap),
          _replaced(ref_train, "follow", follow_checked)):
        result = train.run(c, a, devices, keep)
    if len(chosen) != check["steps"] or set(found) != set(SELECT):
        raise RuntimeError(UNUSED)
    _add(result, found, check["limits"], keep)
    return result


def _add(result: dict, values: dict, limits: dict, keep: dict | None) -> None:
    """The choice's numbers into the result's verdict and table."""
    held = {k: limits[k] for k in values if k in limits}
    ok, table = compare.verdict(values, held)
    for k, v in values.items():
        table.setdefault(k, {"value": v, "limit": None})
    result["correct"] = bool(result["correct"] and ok)
    result["compared"].update(table)
    if keep is not None:
        keep["limits"].update(held)


def select_control(c: dict, seed: int, batches) -> dict:
    """``select_mismatch`` and ``select_gap`` of the reference's own choice
    from fp8 operands (the nearest precision below the configuration's
    bfloat16) in the program's place, at the seeded weights on the first
    batch compared: what the margin is set against."""
    config = c["config"]
    differ = families.load(config["family"]).reference.selection_check(
        config["model"], c["cell"]["check"]["select_margin"],
        control=Numerics("fp8"))
    params, _ = ref_train.programs(config)[0](weights.seed_key(seed))
    return dict(zip(SELECT, differ(params, batches[0]["tokens"])))
