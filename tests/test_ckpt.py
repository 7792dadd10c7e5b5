import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpucfn.ckpt import CheckpointManager
from tpucfn.mesh import MeshSpec, build_mesh
from tpucfn.parallel import ShardingRules, shard_batch
from tpucfn.train import Trainer


def _init(rng):
    return {"w": jax.random.normal(rng, (8, 4)), "b": jnp.zeros((4,))}, {}


def _loss(params, mstate, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2), ({}, mstate)


def _trainer(mesh, rules=None):
    rules = rules or ShardingRules(((r".*", P()),))
    return Trainer(mesh, rules, _loss, optax.adam(1e-2), _init)


def _batch(mesh):
    rs = np.random.RandomState(0)
    return shard_batch(mesh, {"x": rs.randn(16, 8).astype(np.float32),
                              "y": rs.randn(16, 4).astype(np.float32)})


def test_save_restore_roundtrip(tmp_path, mesh_dp8):
    trainer = _trainer(mesh_dp8)
    state = trainer.init(jax.random.key(0))
    for _ in range(3):
        state, _ = trainer.step(state, _batch(mesh_dp8))
    with CheckpointManager(tmp_path / "ckpt") as mgr:
        mgr.save(int(state.step), state)
        mgr.wait()
        restored = mgr.restore(trainer.abstract_state())
    assert int(restored.step) == 3
    np.testing.assert_allclose(
        np.asarray(restored.params["w"]), np.asarray(state.params["w"]), rtol=1e-6
    )
    # training continues bit-for-bit from the restored state
    s1, m1 = trainer.step(state, _batch(mesh_dp8))
    trainer2 = _trainer(mesh_dp8)
    trainer2.init(jax.random.key(1))  # prime shardings, different weights
    s2, m2 = trainer2.step(restored, _batch(mesh_dp8))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)


def test_restore_onto_different_mesh(tmp_path):
    """Save sharded on fsdp=2, restore onto fsdp=4 — the resize/resume path
    (SURVEY.md §3.5 / §7.4 item 2)."""
    rules = ShardingRules(((r"w$", P("fsdp")), (r".*", P())))
    mesh_a = build_mesh(MeshSpec(data=4, fsdp=2))
    tr_a = _trainer(mesh_a, rules)
    state = tr_a.init(jax.random.key(0))
    state, _ = tr_a.step(state, _batch(mesh_a))
    with CheckpointManager(tmp_path / "ckpt") as mgr:
        mgr.save(1, state)
        mgr.wait()
        w_saved = np.asarray(state.params["w"])

        mesh_b = build_mesh(MeshSpec(data=2, fsdp=4))
        tr_b = _trainer(mesh_b, rules)
        restored = mgr.restore(tr_b.abstract_state())
    assert restored.params["w"].sharding.mesh.shape["fsdp"] == 4
    np.testing.assert_allclose(np.asarray(restored.params["w"]), w_saved, rtol=1e-6)


def test_moe_restore_onto_expert_sharded_mesh(tmp_path):
    """Resize/resume for MoE: a checkpoint trained WITHOUT expert
    parallelism (expert axis 1, implicit dispatch) restores onto an
    expert=4 mesh and continues training through the explicit
    all-to-all dispatch — the param tree is identical, only placement
    and dispatch change (SURVEY.md §3.5 resize semantics)."""
    import dataclasses

    from tpucfn.models.llama import (Llama, LlamaConfig, causal_lm_loss,
                                     sharding_rules)
    from tpucfn.models.moe import MoEConfig, collect_moe_aux

    cfg = dataclasses.replace(
        LlamaConfig.tiny(),
        moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0))
    sample = jnp.zeros((2, 16), jnp.int32)

    def make_trainer(mesh, model):
        def init_fn(rng):
            return model.init(rng, sample)["params"], {}

        def loss_fn(params, mstate, batch, rng):
            logits, muts = model.apply({"params": params}, batch["tokens"],
                                       mutable=["losses", "metrics"])
            loss, acc = causal_lm_loss(logits, batch["tokens"])
            return loss + collect_moe_aux(muts), ({"accuracy": acc}, mstate)

        return Trainer(mesh, sharding_rules(cfg, tensor=False), loss_fn,
                       optax.adamw(3e-3), init_fn)

    toks = {"tokens": np.random.RandomState(0).randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32)}

    mesh_a = build_mesh(MeshSpec(data=8))  # no expert sharding
    tr_a = make_trainer(mesh_a, Llama(cfg))
    state = tr_a.init(jax.random.key(0))
    state, _ = tr_a.step(state, shard_batch(mesh_a, toks))
    with CheckpointManager(tmp_path / "ckpt") as mgr:
        mgr.save(1, state)
        mgr.wait()

        mesh_b = build_mesh(MeshSpec(data=2, expert=4))
        tr_b = make_trainer(mesh_b, Llama(cfg, ep_mesh=mesh_b))
        restored = mgr.restore(tr_b.abstract_state())
    wk = restored.params["layers"]["mlp"]["experts/gate_proj/kernel"]
    # by what the sharding means, not how jax spells it (a size-1 fsdp
    # axis may be dropped from the restored spec)
    assert wk.sharding.is_equivalent_to(
        NamedSharding(mesh_b, P(None, "expert", "fsdp")), wk.ndim)
    first = None
    for _ in range(4):
        restored, m = tr_b.step(restored, shard_batch(mesh_b, toks))
        first = first if first is not None else float(m["loss"])
    assert np.isfinite(float(m["loss"])) and float(m["loss"]) < first


def test_prngkey_state_roundtrips(tmp_path, mesh_dp8):
    """Typed PRNG keys (jax.random.key — extended key<fry> dtype) survive
    save/restore: orbax can't serialize them, so the manager splits to
    uint32 key data on save and rewraps on restore (ISSUE 4 satellite —
    resume-from-latest needs the rng back, not a crash)."""
    from tpucfn.ckpt import (rewrap_prng_keys, split_prng_keys,
                             split_prng_keys_abstract)

    trainer = _trainer(mesh_dp8)
    state = trainer.init(jax.random.key(42))
    assert jnp.issubdtype(state.rng.dtype, jax.dtypes.prng_key)
    with CheckpointManager(tmp_path / "ckpt") as mgr:
        assert mgr.save(0, state, force=True)
        mgr.wait()
        restored = mgr.restore(trainer.abstract_state())
    # the key came back typed, same impl, same bits
    assert restored.rng.dtype == state.rng.dtype
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(restored.rng)),
        np.asarray(jax.random.key_data(state.rng)))
    # ...and drives the identical random stream (fold_in(step) in _step_fn)
    np.testing.assert_array_equal(
        np.asarray(jax.random.normal(jax.random.fold_in(restored.rng, 1), (4,))),
        np.asarray(jax.random.normal(jax.random.fold_in(state.rng, 1), (4,))))

    # the split/rewrap helpers are lossless and only touch key leaves
    split = split_prng_keys(state)
    assert split.rng.dtype == jnp.uint32
    assert split.params["w"] is state.params["w"]
    ab = split_prng_keys_abstract(trainer.abstract_state())
    assert ab.rng.dtype == jnp.uint32
    assert ab.rng.shape == split.rng.shape
    back = rewrap_prng_keys(split, trainer.abstract_state())
    assert back.rng.dtype == state.rng.dtype


def test_stale_tmp_dirs_swept_fresh_ones_kept(tmp_path, mesh_dp8):
    """Manager init sweeps abandoned ``*.orbax-checkpoint-tmp-*`` dirs (a
    SIGKILLed rank's half-written save) but must NOT touch one a peer
    rank is actively writing — every gang rank opens a manager on the
    shared directory, and sweeping a live save crashes the saver (and
    the sweeper, racing tensorstore's lock files)."""
    import os
    import time as _time

    d = tmp_path / "ckpt"
    d.mkdir()
    stale = d / "5.orbax-checkpoint-tmp-1000"
    stale.mkdir()
    (stale / "chunk").write_text("partial")
    old = _time.time() - 3600
    os.utime(stale / "chunk", (old, old))
    os.utime(stale, (old, old))
    live = d / "7.orbax-checkpoint-tmp-2000"
    live.mkdir()
    (live / "chunk").write_text("in flight")  # fresh mtime
    with CheckpointManager(d) as mgr:
        assert not stale.exists(), "abandoned tmp dir should be swept"
        assert live.exists(), "a peer's in-flight save must be left alone"
        assert mgr.latest_step() is None  # tmp dirs are not steps


def test_latest_step_and_missing(tmp_path, mesh_dp8):
    trainer = _trainer(mesh_dp8)
    state = trainer.init(jax.random.key(0))
    with CheckpointManager(tmp_path / "c") as mgr:
        assert mgr.latest_step() is None
        with pytest.raises(FileNotFoundError):
            mgr.restore(trainer.abstract_state())
        mgr.save(1, state)
        mgr.save(2, state)
        mgr.wait()
        assert mgr.latest_step() == 2


def test_blacklist_steers_latest_and_restore(tmp_path, mesh_dp8):
    """Step blacklist (ISSUE 7): the manager treats blacklisted steps as
    nonexistent for latest-step selection, so the coordinator's
    corruption retry resumes from the PREVIOUS finalized step — and a
    relaunched rank picks the set up from TPUCFN_CKPT_BLACKLIST."""
    import os

    trainer = _trainer(mesh_dp8)
    state = trainer.init(jax.random.key(0))
    states = {}
    with CheckpointManager(tmp_path / "c") as mgr:
        for s in [1, 2, 3]:
            mgr.save(s, state)
            states[s] = state
            state, _ = trainer.step(state, _batch(mesh_dp8))
        mgr.wait()
    with CheckpointManager(tmp_path / "c", blacklist_steps=[3]) as mgr:
        assert mgr.latest_step() == 2
        restored = mgr.restore(trainer.abstract_state())
        assert int(restored.step) == int(states[2].step)
        # naming a blacklisted step explicitly is still honored — the
        # blacklist steers selection, it does not hide data
        assert int(mgr.restore(trainer.abstract_state(), step=3).step) \
            == int(states[3].step)
    # env fan-out form (what the coordinator's relaunch uses)
    os.environ["TPUCFN_CKPT_BLACKLIST"] = "3, 2,junk"
    try:
        with CheckpointManager(tmp_path / "c") as mgr:
            assert mgr.blacklist_steps == frozenset({2, 3})
            assert mgr.latest_step() == 1
    finally:
        del os.environ["TPUCFN_CKPT_BLACKLIST"]
    # everything blacklisted -> no restore target left
    with CheckpointManager(tmp_path / "c",
                           blacklist_steps=[1, 2, 3]) as mgr:
        assert mgr.latest_step() is None
        with pytest.raises(FileNotFoundError):
            mgr.restore(trainer.abstract_state())


def test_max_to_keep_gc(tmp_path, mesh_dp8):
    trainer = _trainer(mesh_dp8)
    state = trainer.init(jax.random.key(0))
    with CheckpointManager(tmp_path / "c", max_to_keep=2) as mgr:
        for s in [1, 2, 3, 4]:
            mgr.save(s, state)
        mgr.wait()
        assert mgr.latest_step() == 4
        with pytest.raises(Exception):
            mgr.restore(trainer.abstract_state(), step=1)  # GC'd
