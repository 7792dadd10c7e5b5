#!/usr/bin/env python
"""Podracer RL plane benchmark (tpucfn.rl), ONE JSON line out in the
standard BENCH row schema — rc-gated.

Two legs over the identical workload (same env, same policy net, same
number of updates), which isolates exactly what co-location buys:

* **co-located** (the headline): the real plane — rollout is ONE jitted
  ``lax.scan`` program on the mesh, the slab goes through the on-device
  replay ring, and param refresh is a device-to-device copy.  Produces
  ``rl_env_steps_per_sec``.
* **host-roundtrip reference**: the layout Anakin replaced — a host
  loop drives the env one step at a time (separate jit dispatches for
  policy and env step, reward synced to host every step), assembles the
  trajectory slab host-side, feeds the learner via host transfer, and
  refreshes actor params through a device→host→device bounce.

Gates (rc 1 on violation):

* co-located env-steps/s >= ``--min-ratio`` x the host-roundtrip
  reference (the co-location floor; default 1.5x holds easily on the
  8-fake-device CPU mesh because dispatch+sync overhead dominates).
* mean device-to-device refresh latency <= ``--refresh-budget-ms``
  (regression alarm for the copy program growing a host bounce or a
  recompile; steady-state is sub-millisecond for the bench policy).

Compile warmup is excluded from every timed window:
each leg's programs run once on their exact shapes before timing.

``vs_baseline`` is 0.0: the reference repo was a supervised-training
harness with no RL number to compare against.

Usage: python benches/rl_bench.py [--quick] [--iters 30 ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _build(args):
    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.rl import Actor, ReplayQueue, RLLearner, make_env

    mesh = build_mesh(MeshSpec.for_devices(jax.device_count()))
    env = make_env(args.env, args.num_envs)
    learner = RLLearner(mesh, env, hidden=args.hidden)
    actor = Actor(env, learner.apply_fn, unroll=args.unroll)
    queue = ReplayQueue(capacity=2)
    return mesh, env, learner, actor, queue


def _colocated_leg(args, mesh, env, learner, actor, queue):
    """The real plane: scan rollout -> device ring -> learner -> d2d
    refresh, in the loop's exact mesh layout (actor plane pinned via
    ``actor_plane_shardings`` — un-pinned inputs would make GSPMD
    re-shard around every rollout and wreck the number).
    Returns (env_steps_per_s, refresh_latencies_s)."""
    from tpucfn.rl.loop import actor_plane_shardings

    env_sh, slot_sh, repl = actor_plane_shardings(mesh, env.num_envs)
    root = jax.random.key(args.seed)
    state = learner.init(jax.random.fold_in(root, 0))
    es, obs = actor.reset(jax.random.fold_in(root, 1))
    es, obs = jax.device_put((es, obs), env_sh)
    params = learner.refresh(state)
    # warmup: compile every program on its exact shapes + shardings
    es_w, obs_w, traj = actor.rollout(params, es, obs,
                                      jax.random.fold_in(root, 2))
    qs = queue.init_state(traj)
    qs = {k: jax.device_put(v, slot_sh if k == "slots" else repl)
          for k, v in qs.items()}
    qs = queue.push(qs, traj)
    qs, slab = queue.pop(qs)
    state, _ = learner.step(state, slab)
    params = learner.refresh(state)
    jax.block_until_ready(params)

    refresh_lat = []
    t0 = time.perf_counter()
    for it in range(args.iters):
        es, obs, traj = actor.rollout(params, es, obs,
                                      jax.random.fold_in(root, 3 + it))
        qs = queue.push(qs, traj)
        qs, slab = queue.pop(qs)
        state, metrics = learner.step(state, slab)
        r0 = time.perf_counter()
        params = learner.refresh(state)
        jax.block_until_ready(params)
        refresh_lat.append(time.perf_counter() - r0)
        jax.block_until_ready(metrics["loss"])
    wall = time.perf_counter() - t0
    steps = args.iters * actor.steps_per_rollout
    return steps / wall, refresh_lat


def _host_roundtrip_leg(args, mesh, env, learner):
    """The pre-Anakin layout: host drives every env step, the slab and
    the refreshed params both bounce through host memory.  Everything
    still lives on the SAME mesh in the same (replicated) layout as the
    co-located leg — on a real pod the host-driven actor doesn't get a
    smaller device footprint, it gets per-step dispatch and sync on the
    same one — so the legs differ only in orchestration."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpucfn.rl.learner import mlp_apply

    repl = NamedSharding(mesh, P())
    apply_j = jax.jit(mlp_apply)
    step_j = jax.jit(env.step)
    sample_j = jax.jit(
        lambda k, logits: jax.random.categorical(k, logits))
    root = jax.random.key(args.seed)
    state = learner.init(jax.random.fold_in(root, 0))

    def host_refresh(state):
        # device -> host -> device: what refresh() exists to avoid
        return jax.device_put(jax.tree.map(np.asarray, state.params), repl)

    params = host_refresh(state)
    es, obs = jax.jit(env.reset)(jax.random.fold_in(root, 1))
    es, obs = jax.device_put((es, obs), repl)

    def host_rollout(params, es, obs, key):
        cols = {k: [] for k in ("obs", "action", "reward", "done", "value")}
        for t in range(args.unroll):
            logits, value = apply_j(params, obs)
            k_act, k_env = jax.random.split(jax.random.fold_in(key, t))
            action = sample_j(k_act, logits)
            es2, obs2, reward, done = step_j(es, action, k_env)
            # the host loop inspects progress every step: a forced sync
            cols["obs"].append(np.asarray(obs))
            cols["action"].append(np.asarray(action))
            cols["reward"].append(np.asarray(reward))
            cols["done"].append(np.asarray(done))
            cols["value"].append(np.asarray(value))
            es, obs = es2, obs2
        traj = {k: np.stack(v, axis=1) for k, v in cols.items()}
        _, bootstrap = apply_j(params, obs)
        traj["bootstrap"] = np.asarray(bootstrap)
        return es, obs, traj

    # warmup (same programs, exact shapes)
    es_w, obs_w, traj = host_rollout(params, es, obs,
                                     jax.random.fold_in(root, 2))
    state, _ = learner.step(state, jax.device_put(traj))
    params = host_refresh(state)

    t0 = time.perf_counter()
    for it in range(args.iters):
        es, obs, traj = host_rollout(params, es, obs,
                                     jax.random.fold_in(root, 3 + it))
        state, metrics = learner.step(state, jax.device_put(traj))
        params = host_refresh(state)
        jax.block_until_ready(metrics["loss"])
    wall = time.perf_counter() - t0
    steps = args.iters * args.unroll * args.num_envs
    return steps / wall


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--env", choices=["bandit", "gridworld"],
                   default="gridworld")
    p.add_argument("--num-envs", type=int, default=8)
    p.add_argument("--unroll", type=int, default=32)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--min-ratio", type=float, default=1.5,
                   help="rc gate: co-located steps/s must beat the "
                        "host-roundtrip reference by this factor")
    p.add_argument("--refresh-budget-ms", type=float, default=50.0,
                   help="rc gate: mean d2d refresh latency bound")
    p.add_argument("--quick", action="store_true",
                   help="CI sizing (fewer iterations, same gates)")
    args = p.parse_args()
    if args.quick:
        args.iters = min(args.iters, 10)

    mesh, env, learner, actor, queue = _build(args)
    colocated_sps, refresh_lat = _colocated_leg(args, mesh, env, learner,
                                                actor, queue)
    host_sps = _host_roundtrip_leg(args, mesh, env, learner)

    ratio = colocated_sps / host_sps if host_sps > 0 else float("inf")
    refresh_mean_ms = 1e3 * float(np.mean(refresh_lat))
    refresh_p50_ms = 1e3 * float(np.percentile(refresh_lat, 50))
    ratio_ok = ratio >= args.min_ratio
    refresh_ok = refresh_mean_ms <= args.refresh_budget_ms
    ok = ratio_ok and refresh_ok

    print(f"# rl_bench colocated={colocated_sps:.0f} steps/s "
          f"host_roundtrip={host_sps:.0f} steps/s ratio={ratio:.2f} "
          f"(floor {args.min_ratio}) refresh_mean={refresh_mean_ms:.3f}ms "
          f"(budget {args.refresh_budget_ms}ms) ok={ok}", file=sys.stderr)
    row = {
        "metric": "rl_env_steps_per_sec",
        "value": round(colocated_sps, 1),
        "unit": "steps/s",
        "vs_baseline": 0.0,
        "detail": {
            "baseline_note": "reference harness was supervised-training "
                             "only; no RL throughput number exists",
            "ok": ok,
            "env": args.env,
            "num_envs": args.num_envs,
            "unroll": args.unroll,
            "iters": args.iters,
            "devices": jax.device_count(),
            "colocated_steps_per_s": round(colocated_sps, 1),
            "host_roundtrip_steps_per_s": round(host_sps, 1),
            "colocation_ratio": round(ratio, 3),
            "min_ratio": args.min_ratio,
            "ratio_ok": ratio_ok,
            "refresh_mean_ms": round(refresh_mean_ms, 4),
            "refresh_p50_ms": round(refresh_p50_ms, 4),
            "refresh_budget_ms": args.refresh_budget_ms,
            "refresh_ok": refresh_ok,
        },
    }
    print(json.dumps(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
