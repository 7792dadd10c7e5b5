#!/usr/bin/env python
"""JoyAI-LLM-Flash (48B-A2.7B) training: DeepSeek-V3's layers at small widths
(latent attention in every block, one leading dense layer, a sigmoid router
with a selection bias over 256 narrow experts, a multi-token-prediction
block) through ``models/latent.LatentDecoder``.

    tpucfn launch examples/joyai_llm_flash.py -- \
        --model ep16 --batch-size 2 --seq-len 8192

``--model ep16`` is one chip's share of a layer that sixteen chips divide:
the leading dense layer, 4 sparse layers and the prediction block at the
published widths, the first 16 of the 256 experts (the router keeps its 256
outputs, its bias and its 8 a token; what the other experts would add is
another chip's part), one of eight slices of the vocabulary.  ``ep8`` holds 32
experts and needs 14.8 of one v5e chip's 15.75 GB at 2 x 8,192 tokens.  The
expert layer makes no exchange here.  ``--model tiny`` runs the identical
program shape on CPU/CI.  The step's counters (``lm_loss``, ``mtp_loss``,
``moe_rows``, ``moe_load_max_over_mean``, ``moe_dropped``, ``moe_blocks_run``)
go to the log, the trace (``step_metrics``) and the ``train_*`` gauges.
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
    p.add_argument("--model", default="tiny", choices=["ep16", "ep8", "tiny"])
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
    from tpucfn.models.latent import (LatentConfig, LatentDecoder, make_loss_fn,
                                      sharding_rules)
    from tpucfn.train import Trainer

    # 1 dense + 4 sparse layers, an eighth of the vocabulary
    share = LatentConfig(vocab_size=16160, n_layers=5)
    cfg = {
        "ep16": lambda: dataclasses.replace(share, held_experts=(0, 16)),
        "ep8": lambda: dataclasses.replace(share, held_experts=(0, 32)),
        # half of the 8 experts
        "tiny": lambda: dataclasses.replace(LatentConfig.tiny(),
                                            held_experts=(0, 4)),
    }[args.model]()

    run_dir = Path(args.run_dir)
    shards = stage_synthetic(
        "tokens", run_dir / "data", n=args.num_examples,
        num_shards=max(8, jax.process_count()), seed=args.seed,
        seq_len=args.seq_len, vocab=cfg.vocab_size)
    mesh = build_mesh(MeshSpec.for_devices(jax.device_count(), fsdp=args.fsdp))
    model = LatentDecoder(cfg)
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
