"""Drives a rehearsal run with the timed path broken underneath: the tests
start it as a process of its own.  ``python fault_driver.py <fault> <args of
benchmark.run>``; the faults are planted in the program's ``Trainer``, under
the harness, which is left as it is."""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def plant(fault: str) -> None:
    import jax

    from tpucfn.train import trainer as T

    if fault == "state_unchanged":
        real = T.Trainer._step_fn

        def step_fn(self, state, batch):
            new, metrics = real(self, state, batch)
            return dataclasses.replace(state, step=new.step), metrics

        T.Trainer._step_fn = step_fn
    elif fault == "half_batch":
        real = T.Trainer._grads

        def grads(self, state, batch, rng):
            def cut(x):
                return x[: x.shape[0] // 2] if x.shape[0] >= 2 else x[:, : x.shape[1] // 2]
            return real(self, state, jax.tree.map(cut, batch), rng)

        T.Trainer._grads = grads
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    from benchmark import run

    raise SystemExit(run.main(sys.argv[2:]))
