#!/usr/bin/env python
"""Granite-4.0-H-Micro training: nine Mamba-2 state-space mixers to one
attention layer without positional embedding, dense feed-forwards, a tied and
scaled head, through ``models/ssm.SSMDecoder``.

    tpucfn launch examples/granite4_h.py -- \
        --model p1 --batch-size 1 --seq-len 16384

``--model p1`` is one whole period of the published 40 layers at the published
widths (five Mamba layers, the attention layer, four Mamba layers; 951.9 M
parameters with the whole vocabulary): what one of four pipeline stages would
hold.  ``--model tiny`` runs the identical program shape on CPU/CI.  The step's
counters (``ssm_log_decay_min``, ``ssm_state_rms``) go to the log, the trace
(``step_metrics``) and the ``train_*`` gauges.
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
    from tpucfn.models.ssm import (PERIOD, SSMConfig, SSMDecoder, make_loss_fn,
                                   sharding_rules)
    from tpucfn.train import Trainer

    cfg = {"p1": lambda: SSMConfig(layer_types=PERIOD),
           "tiny": SSMConfig.tiny}[args.model]()

    run_dir = Path(args.run_dir)
    shards = stage_synthetic(
        "tokens", run_dir / "data", n=args.num_examples,
        num_shards=max(8, jax.process_count()), seed=args.seed,
        seq_len=args.seq_len, vocab=cfg.vocab_size)
    mesh = build_mesh(MeshSpec.for_devices(jax.device_count(), fsdp=args.fsdp))
    model = SSMDecoder(cfg)
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
