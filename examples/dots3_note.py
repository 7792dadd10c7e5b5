#!/usr/bin/env python
"""dots3-note-prev (288B-A17B) training: DeepSeek sparse attention (a 64-head
indexer chooses 2,048 keys a query for latent attention, trained by its KL
loss) in the full layers, 513-key windowed latent attention in three of every
four, headwise output gates, and a sigmoid router over 256 narrow experts,
through ``models/dots3_note.Dots3Decoder``.

    tpucfn launch examples/dots3_note.py -- \
        --model tp4-ep32 --batch-size 1 --seq-len 16384

``--model tp4-ep32`` is one chip's share of a layer that 32 chips divide: the
leading dense layer and one period (a sparse-attention layer and three window
layers) at the published widths, a quarter of the heads (32 of 128 full, 16 of
64 window; latent down-projections, indexer, router and shared expert whole),
the first 8 of the 256 experts (the router keeps its 256 outputs, its bias and
its 8 a token), an eighth of the vocabulary.  ``tp8-ep32`` holds an eighth of
the heads.  Nothing stands in for the absent chips.  ``--model tiny`` runs the
identical program shape on CPU/CI.  The step's counters (``lm_loss``,
``dsa_kl``, ``dsa_select_share``, ``moe_rows``, ``moe_load_max_over_mean``,
``moe_dropped``, ``moe_blocks_run``) go to the log, the trace
(``step_metrics``) and the ``train_*`` gauges.
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
    p.add_argument("--model", default="tiny", choices=["tp4-ep32", "tp8-ep32", "tiny"])
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
    from tpucfn.models.dots3_note import (Dots3Config, Dots3Decoder,
                                          make_loss_fn, sharding_rules)
    from tpucfn.train import Trainer

    # the leading dense layer and one period, 8 of 256 experts, an eighth of
    # the vocabulary
    share = Dots3Config(vocab_size=19008, held_experts=(0, 8))
    cfg = {
        "tp4-ep32": lambda: dataclasses.replace(share, n_heads=32, swa_heads=16),
        "tp8-ep32": lambda: dataclasses.replace(share, n_heads=16, swa_heads=8),
        # half of the 8 experts; 12 keys a query, so that the choice drops some
        "tiny": lambda: dataclasses.replace(Dots3Config.tiny(),
                                            held_experts=(0, 4)),
    }[args.model]()

    run_dir = Path(args.run_dir)
    shards = stage_synthetic(
        "tokens", run_dir / "data", n=args.num_examples,
        num_shards=max(8, jax.process_count()), seed=args.seed,
        seq_len=args.seq_len, vocab=cfg.vocab_size)
    mesh = build_mesh(MeshSpec.for_devices(jax.device_count(), fsdp=args.fsdp))
    model = Dots3Decoder(cfg)
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
