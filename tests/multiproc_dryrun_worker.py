"""Worker for the multi-process x multi-device dryrun leg (not a pytest
module).

Spawned by ``__graft_entry__._dryrun_multiprocess`` (and runnable by
hand): N processes x K fake CPU devices each join one
``jax.distributed`` rendezvous and train over global meshes that SPAN
the process boundary — the actual multihost TPU execution model
(SURVEY.md §4 "Multi-process without a cluster"). Two legs:

* ``MPLEG`` — (data:2, fsdp:4) MLP; loss must match the single-process
  control bit-for-bit.
* ``MPLEG2`` — (expert:4, tensor:2) MoE: the expert axis (and its
  all-to-all dispatch) stretches across processes; loss must match the
  control to a small fp tolerance (the two layouts compile different
  executables, so reduce orders differ — ~5e-7 observed).

The same file run with ``TPUCFN_MP_NPROC=1`` and 8 local devices is the
single-process control; the parent does the comparisons.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    _n = int(os.environ.get("TPUCFN_MP_LOCAL_DEVICES", "4"))
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n}"
    )

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def _init(rng):
    k1, k2 = jax.random.split(rng)
    params = {
        "fc1": {"kernel": jax.random.normal(k1, (4, 32)) * 0.1,
                "bias": jnp.zeros(32)},
        "fc2": {"kernel": jax.random.normal(k2, (32, 1)) * 0.1,
                "bias": jnp.zeros(1)},
    }
    return params, {}


def _loss(params, model_state, batch, rng):
    h = jnp.tanh(batch["x"] @ params["fc1"]["kernel"] + params["fc1"]["bias"])
    pred = h @ params["fc2"]["kernel"] + params["fc2"]["bias"]
    loss = jnp.mean((pred[:, 0] - batch["y"]) ** 2)
    return loss, ({}, model_state)


def main() -> int:
    rank = int(os.environ.get("TPUCFN_MP_RANK", "0"))
    nproc = int(os.environ.get("TPUCFN_MP_NPROC", "1"))
    if nproc > 1:
        jax.distributed.initialize(os.environ["TPUCFN_MP_COORD"],
                                   num_processes=nproc, process_id=rank)

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.parallel import ShardingRules, shard_batch
    from tpucfn.train import Trainer

    assert jax.process_count() == nproc, (jax.process_count(), nproc)
    assert jax.device_count() == 8, jax.device_count()

    mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    rules = ShardingRules(((r"(fc1|fc2)/kernel$", P("fsdp")), (r".*", P())))
    trainer = Trainer(mesh, rules, _loss, optax.sgd(0.1), _init)
    state = trainer.init(jax.random.key(0))

    # The fsdp-sharded kernel is one GLOBAL array; this process addresses
    # only the shards on its local devices.
    k = state.params["fc1"]["kernel"]
    assert k.sharding.spec == P("fsdp"), k.sharding.spec
    assert len(k.addressable_shards) == 8 // nproc, len(k.addressable_shards)

    # Deterministic global batch; each process feeds its contiguous rows
    # (data index p = process p's devices under row-major mesh layout).
    rs = np.random.RandomState(0)
    x = rs.randn(64, 4).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5, 0.0], np.float32)).astype(np.float32)
    lo, hi = rank * 64 // nproc, (rank + 1) * 64 // nproc
    batch = shard_batch(mesh, {"x": x[lo:hi], "y": y[lo:hi]})

    metrics = {}
    for _ in range(3):
        state, metrics = trainer.step(state, batch)
    print(f"MPLEG rank={rank} nproc={nproc} loss={float(metrics['loss']):.12f}",
          flush=True)

    # Leg 2 (round 5): expert parallelism SPANNING the process boundary.
    # Axis order puts data/fsdp outer, so a (expert:4, tensor:2) mesh
    # stretches the expert axis across the 2-process layout (experts
    # 0-1 on process 0, 2-3 on process 1): the MoE dispatch's
    # lax.all_to_all is a genuine cross-process collective, and the
    # parent asserts the loss equals the single-process layout's.
    import dataclasses

    from tpucfn.models.llama import (Llama, LlamaConfig, causal_lm_loss,
                                     sharding_rules)
    from tpucfn.models.moe import MoEConfig, collect_moe_aux

    mesh2 = build_mesh(MeshSpec(expert=4, tensor=2))
    # tiny()'s 4 heads / 2 kv-heads already divide the tensor axis.
    cfg = dataclasses.replace(
        LlamaConfig.tiny(),
        moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0))
    model = Llama(cfg, ep_mesh=mesh2)
    sample = jnp.zeros((4, 16), jnp.int32)

    def init2(rng):
        return model.init(rng, sample)["params"], {}

    def loss2(params, mstate, batch, rng):
        logits, muts = model.apply({"params": params}, batch["tokens"],
                                   mutable=["losses", "metrics"])
        loss, acc = causal_lm_loss(logits, batch["tokens"])
        return loss + collect_moe_aux(muts), ({"accuracy": acc}, mstate)

    trainer2 = Trainer(mesh2, sharding_rules(cfg), loss2, optax.sgd(0.05),
                      init2)
    state2 = trainer2.init(jax.random.key(1))
    toks = np.random.RandomState(1).randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32)
    lo2, hi2 = rank * 8 // nproc, (rank + 1) * 8 // nproc
    batch2 = shard_batch(mesh2, {"tokens": toks[lo2:hi2]})
    m2 = {}
    for _ in range(2):
        state2, m2 = trainer2.step(state2, batch2)
    print(f"MPLEG2 rank={rank} nproc={nproc} loss={float(m2['loss']):.12f}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
