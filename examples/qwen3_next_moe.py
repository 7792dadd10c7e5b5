#!/usr/bin/env python
"""Qwen3-Next-80B-A3B training: a hybrid decoder (three Gated DeltaNet layers
to one gated-attention layer, every feed-forward sparse) through
``models/hybrid.HybridDecoder``, the period-scanned decoder.

    tpucfn launch examples/qwen3_next_moe.py -- \
        --model ep8 --batch-size 2 --seq-len 8192

``--model ep8`` is one chip's share of a layer that eight chips divide: one
whole period at the published widths, the first 64 of the 512 experts (the
router keeps its 512 outputs and its 10 a token, and what the other experts
would add is another chip's part), one of eight slices of the vocabulary.
The expert layer makes no exchange here.  ``--model tiny`` runs the
identical program shape on CPU/CI.  The step's routing counters
(``moe_rows``, ``moe_load_max_over_mean``, ``moe_dropped``, ``moe_blocks_run``)
go to the log, the trace (``step_metrics``) and the ``train_moe_*`` gauges.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))
from common import (  # noqa: E402
    add_cluster_args,
    per_process_batch,
    run_train_loop,
    stage_synthetic,
)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    add_cluster_args(p)
    p.add_argument("--model", default="tiny", choices=["ep8", "tiny"])
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--num-examples", type=int, default=256)
    args = p.parse_args()

    from tpucfn.launch import initialize_runtime

    initialize_runtime()

    import jax
    import jax.numpy as jnp
    import optax

    from tpucfn.data import ShardedDataset
    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.models.hybrid import (HybridConfig, HybridDecoder, make_loss_fn,
                                      sharding_rules)
    from tpucfn.train import Trainer

    cfg = {
        # one period, 64 of 512 experts, an eighth of the vocabulary
        "ep8": lambda: HybridConfig(vocab_size=18992, n_layers=4,
                                    held_experts=(0, 64)),
        # two periods, half of the 8 experts
        "tiny": lambda: dataclasses.replace(
            HybridConfig.tiny(), n_layers=8, held_experts=(0, 4)),
    }[args.model]()

    run_dir = Path(args.run_dir)
    shards = stage_synthetic(
        "tokens", run_dir / "data", n=args.num_examples,
        num_shards=max(8, jax.process_count()), seed=args.seed,
        seq_len=args.seq_len, vocab=cfg.vocab_size)
    mesh = build_mesh(MeshSpec.for_devices(jax.device_count(), fsdp=args.fsdp))
    model = HybridDecoder(cfg)
    dp = mesh.shape["data"] * mesh.shape["fsdp"] * mesh.shape["expert"]
    sample = jnp.zeros((dp, args.seq_len), jnp.int32)

    def init_fn(rng):
        return model.init(rng, sample)["params"], {}

    # Adafactor: AdamW's state does not fit one chip beside 64 experts a layer
    trainer = Trainer(mesh, sharding_rules(cfg),
                      make_loss_fn(model),
                      optax.adafactor(1e-3), init_fn)
    ds = ShardedDataset(shards, batch_size_per_process=per_process_batch(args),
                        seed=args.seed)
    run_train_loop(trainer, ds, mesh, args,
                   items_per_step=args.batch_size * args.seq_len)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
