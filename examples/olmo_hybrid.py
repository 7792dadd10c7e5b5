#!/usr/bin/env python
"""Olmo-Hybrid-7B training: three Gated DeltaNet layers with negative
eigenvalues (keys of 96, values of 192) to one full-attention layer without
positions, every layer in Olmo's reordered block, through
``models/olmo_hybrid.OlmoHybridDecoder``.

    tpucfn launch examples/olmo_hybrid.py -- \
        --model p1 --batch-size 1 --seq-len 16384

``--model p1`` is one whole period of the published 32 layers at the published
widths with a quarter of the vocabulary (25,088 of 100,352 rows: 1,025 M
parameters), what one of eight pipeline stages holds where four chips share the
embedding and the head by rows; ``--layers`` takes another whole number of
periods.  ``--model tiny`` runs the identical program shape on CPU/CI.  The
step's counters (``gdn_reflect_share``, ``gdn_state_rms``) go to the log, the
trace (``step_metrics``) and the ``train_*`` gauges.
"""

from __future__ import annotations

import argparse
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
    p.add_argument("--model", default="tiny", choices=["p1", "tiny"])
    p.add_argument("--layers", type=int, default=4,
                   help="p1's depth, a whole number of periods of 4")
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
    from tpucfn.models.olmo_hybrid import (OlmoHybridConfig, OlmoHybridDecoder,
                                           make_loss_fn, sharding_rules)
    from tpucfn.train import Trainer

    cfg = {"p1": lambda: OlmoHybridConfig(n_layers=args.layers, vocab_size=25088),
           "tiny": OlmoHybridConfig.tiny}[args.model]()

    run_dir = Path(args.run_dir)
    shards = stage_synthetic(
        "tokens", run_dir / "data", n=args.num_examples,
        num_shards=max(8, jax.process_count()), seed=args.seed,
        seq_len=args.seq_len, vocab=cfg.vocab_size)
    mesh = build_mesh(MeshSpec.for_devices(jax.device_count(), fsdp=args.fsdp))
    model = OlmoHybridDecoder(cfg)
    dp = mesh.shape["data"] * mesh.shape["fsdp"] * mesh.shape["expert"]
    sample = jnp.zeros((dp, args.seq_len), jnp.int32)

    def init_fn(rng):
        return model.init(rng, sample)["params"], {}

    # Adafactor: AdamW's state leaves no room for 16,384 tokens' activations
    trainer = Trainer(mesh, sharding_rules(cfg), make_loss_fn(model),
                      optax.adafactor(1e-3), init_fn)
    ds = ShardedDataset(shards, batch_size_per_process=per_process_batch(args),
                        seed=args.seed)
    run_train_loop(trainer, ds, mesh, args,
                   items_per_step=args.batch_size * args.seq_len)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
