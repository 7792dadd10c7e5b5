"""Builds the system under test from a cell's files, the way the examples
build it: the same model classes, sharding rules, losses, ``ShardedDataset``
and ``Trainer`` (``benchmark/families/<family>.py``), with the program's
defaults wherever the examples leave a choice to them (no worker count,
prefetch depth, shuffle buffer or environment name is set here).  The one
thing not the examples' is the ``init_fn``: the weights are the benchmark's,
made from ``--seed`` on the device inside the program's own jitted
``train_init``.
"""

from __future__ import annotations

import argparse
from pathlib import Path

import jax

from benchmark import families, weights


def stage(mix: dict, rows: list[dict], data_dir: Path) -> list[Path]:
    from tpucfn.data import write_dataset_shards

    data_dir.mkdir(parents=True, exist_ok=True)
    return write_dataset_shards(iter(rows), data_dir,
                                num_shards=mix["records"]["shards"])


def _init_fn(config: dict):
    ref = families.load(config["family"]).reference
    spec, sspec = ref.param_spec(config["model"]), ref.state_spec(config["model"])

    def init_fn(rng):
        key = weights.key_from_trainer(rng)  # == weights.seed_key(--seed)
        return weights.make(spec, key), weights.make(sspec, key)

    return init_fn


def build(config: dict, mix: dict, cell: dict, shards, seed: int,
          run_dir: Path, devices):
    """(trainer, dataset, mesh, args, items per step) for one run."""
    import examples.common as common  # turns the compile cache on at import
    from tpucfn.data import ShardedDataset
    from tpucfn.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec.for_devices(len(devices)), devices)
    trainer, items = families.load(config["family"]).build(
        config, mix, mesh, _init_fn(config))
    ds = ShardedDataset(shards, batch_size_per_process=mix["shape"]["batch"],
                        seed=weights.seed31(seed),
                        cache_in_memory=mix["input"]["cache_in_memory"])
    p = argparse.ArgumentParser()
    common.add_cluster_args(p)
    args = p.parse_args([
        "--run-dir", str(run_dir),
        "--batch-size", str(mix["shape"]["batch"]),
        "--seed", str(weights.seed31(seed)),
        # the job's, not the program's choice of path: the end of the stream
        # closes the run, and no save falls inside the window
        "--steps", str(cell["loop"]["steps"]),
        "--ckpt-every", str(cell["loop"]["ckpt_every"]),
    ])
    return trainer, ds, mesh, args, items


def kernel_calls(trainer, mesh, host_batch) -> int:
    """How many Pallas kernels the step the window drove has in it, from the
    text of its lowering at the window's own shapes."""
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=trainer.batch_sharding()),
        host_batch)
    text = trainer._jit_step.lower(trainer.abstract_state(), batch).as_text()
    return text.count("tpu_custom_call")
