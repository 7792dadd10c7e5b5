"""Shared plumbing for the bundled examples.

The reference's examples were AMI-shipped scripts driven by README
commands (SURVEY.md §2.1); tpucfn ships them in-repo. Each example is a
normal script that works single-host (`python examples/x.py`) and
multi-host (`tpucfn launch examples/x.py`) with no code change — the
runtime initialization no-ops outside a cluster.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

# Examples are runnable from a bare checkout (`python examples/x.py`)
# without installing the package: put the repo root ahead on sys.path.
_REPO_ROOT = str(Path(__file__).resolve().parent.parent)
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import jax

# Persistent XLA compile cache, armed BEFORE anything compiles: jax
# initializes the compilation cache at most once per process, at the
# FIRST compile — and the examples compile during data staging/mesh
# probing, well before run_train_loop runs.  Setting the dir there was
# too late: the cache initialized path-less and stayed disabled for the
# whole process (warm restarts silently recompiled everything).
from tpucfn.obs import enable_compile_cache  # noqa: E402

enable_compile_cache()


def add_cluster_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--run-dir", default="/tmp/tpucfn-run",
                   help="checkpoints, metrics, staged data land here (≈ the EFS mount)")
    p.add_argument("--batch-size", type=int, default=256, help="GLOBAL batch size")
    p.add_argument("--steps", type=int, default=0,
                   help="hard step cap that IS the run's budget (0 = the "
                        "full epoch budget); LR schedules anneal over it")
    p.add_argument("--stop-after", type=int, default=0,
                   help="halt once the global step reaches N WITHOUT "
                        "changing the budget or LR schedule — a simulated "
                        "interruption/preemption; relaunching resumes the "
                        "same schedule where it stopped (0 = off)")
    p.add_argument("--num-epochs", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--ckpt-every", type=int, default=200)
    p.add_argument("--resume", action="store_true",
                   help="(default behavior, kept for compat) resume from the "
                        "latest checkpoint in --run-dir")
    p.add_argument("--fresh", action="store_true",
                   help="delete existing checkpoints in --run-dir and train "
                        "from step 0")
    p.add_argument("--eval-every", type=int, default=0,
                   help="run validation over the held-out split every N steps")
    p.add_argument("--profile", action="store_true",
                   help="capture a jax.profiler trace of steps 10-20")
    p.add_argument("--profile-server", type=int, default=0, metavar="PORT",
                   help="start the per-host jax profiler server on PORT so "
                        "XProf/TensorBoard can attach a live capture (0=off)")
    # Parallelism surface (reference exposed only worker count; SURVEY §2.3
    # mandates the full set as first-class flags).
    p.add_argument("--kv-store", default="dist_sync",
                   choices=["dist_sync", "device"],
                   help="compat shim: the reference's MXNet flag; both map to "
                        "synchronous DP via psum over ICI")
    p.add_argument("--fsdp", type=int, default=1, help="fsdp axis size")
    p.add_argument("--tensor", type=int, default=1, help="tensor-parallel axis size")


def build_example_mesh(args):
    from tpucfn.mesh import MeshSpec, build_mesh

    n = jax.device_count()
    return build_mesh(MeshSpec.for_devices(n, fsdp=args.fsdp, tensor=args.tensor))


def per_process_batch(args) -> int:
    if args.batch_size % jax.process_count():
        raise SystemExit(
            f"--batch-size {args.batch_size} not divisible by "
            f"{jax.process_count()} processes"
        )
    return args.batch_size // jax.process_count()


def stage_synthetic(kind: str, data_dir: Path, *, n: int, num_shards: int,
                    seed: int = 0, **gen_kwargs):
    """Stage synthetic data once (≈ `aws s3 sync` in the reference README;
    real datasets go through the identical write_dataset_shards path)."""
    from tpucfn.data import (
        synthetic_cifar10,
        synthetic_imagenet,
        synthetic_latents,
        synthetic_tokens,
        write_dataset_shards,
    )

    data_dir.mkdir(parents=True, exist_ok=True)
    existing = sorted(data_dir.glob("*.tpurec"))
    if existing:
        return existing
    gen = {
        "cifar10": synthetic_cifar10,
        "imagenet": synthetic_imagenet,
        "tokens": synthetic_tokens,
        "latents": synthetic_latents,
    }[kind]
    return write_dataset_shards(gen(n, seed=seed, **gen_kwargs), data_dir,
                                num_shards=num_shards)


def run_train_loop(trainer, ds, mesh, args, *, items_per_step, extra_axes=(),
                   eval_ds=None):
    """The shared epoch/step/checkpoint/metrics loop every example uses.

    ``eval_ds`` + ``--eval-every N`` runs inference-mode validation (the
    trainer's eval_loss_fn) over the held-out split and logs ``eval_*``
    metrics — the measurement path for accuracy targets like the 76%
    top-1 north star (BASELINE.md)."""
    import jax

    # Not used in this function, and not to be moved: importing orbax here,
    # before the planes below start their threads, takes 12 s on a TPU host
    # (google.api_core's version check walks site-packages' metadata several
    # times) and 22-26 s from _train_loop_body (PERF.md, Findings, PR 29).
    from tpucfn.ckpt import CheckpointManager  # noqa: F401
    from tpucfn.data import prefetch_to_mesh  # noqa: F401
    from tpucfn.obs import (
        MetricLogger,
        StepTimer,
        Tracer,
        profile_steps,
        set_default_labels,
        start_obs_server,
    )
    from tpucfn.parallel import shard_batch
    from tpucfn.train.trainer import TrainerObs

    from tpucfn.obs import start_profiler_server

    # The compile cache itself was enabled at module import (see top of
    # file — it must precede the process's first compile).
    if getattr(args, "profile_server", 0):
        start_profiler_server(args.profile_server)

    run_dir = Path(args.run_dir)
    if args.fresh:
        # Clear, don't just ignore: stale checkpoints would swallow the
        # fresh run's saves at colliding steps, and the next (auto-resume)
        # relaunch would restore the pre-fresh weights. Process 0 owns
        # the delete (the run dir may be a shared EFS-style mount) and
        # everyone barriers before the CheckpointManager opens.
        delete_err = ""
        if jax.process_index() == 0 and (run_dir / "ckpt").exists():
            import shutil

            try:
                shutil.rmtree(run_dir / "ckpt", ignore_errors=True)
            except OSError as e:  # defensive: ignore_errors should eat these
                delete_err = f"--fresh delete of {run_dir / 'ckpt'} failed: {e}"
            if not delete_err and (run_dir / "ckpt").exists():
                # A silent partial delete would recreate exactly the
                # stale-resume corruption --fresh exists to prevent.
                delete_err = (
                    f"--fresh could not clear {run_dir / 'ckpt'} (shared-"
                    "mount file still held open, or permissions?) — clear "
                    "it manually or use a new --run-dir")
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils

            # The broadcast doubles as the barrier AND carries process 0's
            # outcome: a failed delete must abort the whole gang together,
            # not leave the other processes wedged in a barrier while
            # process 0 unwinds (ADVICE r2).
            failed = int(multihost_utils.broadcast_one_to_all(
                np.int32(1 if delete_err else 0)))
            if failed:
                raise RuntimeError(
                    delete_err or "--fresh checkpoint clear failed on "
                    "process 0 — see its log for the path")
        elif delete_err:
            raise RuntimeError(delete_err)
    timer = StepTimer()
    host = jax.process_index()

    def run_eval(state, step):
        if eval_ds is None or not args.eval_every:
            return
        sums, n = {}, 0
        for host_batch in eval_ds.epoch(0):
            m = trainer.eval_step(state, shard_batch(mesh, host_batch, extra_axes))
            m.pop("counters", None)   # a training step's, not an average's
            for k, v in m.items():
                sums[k] = sums.get(k, 0.0) + float(v)
            n += 1
        if n:
            logger.log(step, {f"eval_{k}": v / n for k, v in sums.items()})

    # try/finally from the FIRST resource on: a failing step, interrupt,
    # or a bind error from the obs endpoint itself must still release
    # the bound port and the open log/trace files — a retry in the same
    # process would otherwise hit "Address already in use".
    logger = tracer = obs_srv = hb = ledger = None
    try:
        logger = MetricLogger(run_dir / "logs", stdout_every=args.log_every)
        # The observability plane (ISSUE 2): registry metrics + trace
        # spans per loop phase, and — when the launcher assigned this
        # process a port (TPUCFN_OBS_PORT) — the per-host
        # /metrics·/healthz·/varz endpoint, so every trainer rank in the
        # fan-out is scrapeable.
        registry = set_default_labels(host=str(host), role="trainer")
        tracer = Tracer(run_dir / "trace", host_id=host, role="trainer")
        # The goodput ledger (ISSUE 5): every loop phase is attributed to
        # a wall-clock bucket in a per-host JSONL; a relaunch appends a
        # new window to the same file, which is how `tpucfn obs goodput`
        # sees restart downtime and post-rewind re-runs.
        from tpucfn.obs.goodput import GoodputLedger

        ledger = GoodputLedger(run_dir / "goodput", host_id=host,
                               role="trainer")
        # The forensics plane (ISSUE 6): a bounded in-memory flight ring
        # of per-phase + HBM samples, dumped to run_dir/flight on
        # SIGTERM/atexit and served live on /flightrecorder (where the
        # gang coordinator fetches it at detect time); device_hbm_*
        # gauges on /metrics (absent on CPU — memory_stats is None); an
        # on-demand profiler capture behind POST /profile.
        from tpucfn.obs import (FlightRecorder, ProfileCapture,
                                register_device_gauges)

        flight = FlightRecorder(host_id=host, role="trainer")
        flight.install_dump_handlers(run_dir / "flight")
        register_device_gauges(
            registry,
            jit_sources=(lambda: trainer._jit_step,
                         lambda: trainer._jit_eval))
        # Fleet warm start (ISSUE 13): when the launcher fanned out
        # artifact-server addresses (TPUCFN_COMPILE_CACHE_ADDRS) — or a
        # local store dir is pinned — the trainer's programs go
        # lower → key → fetch-or-compile, and fetches land a compile_fetch
        # trace span.  Env unset ⇒ None installed: lower → compile.
        from tpucfn.compilecache import configure_from_env

        configure_from_env(tracer=tracer, registry=registry)
        obs = TrainerObs(registry, tracer, ledger=ledger, flight=flight)
        # Every program the trainer compiles writes one step_program span
        # (what it is made of, where its compile came from: the first
        # step's compile / compile_cached / compile_fetched in the ledger).
        trainer.on_program = obs.record_program
        obs_srv = start_obs_server(
            registry, role="trainer", host_id=host,
            health_fn=lambda: (True, {"step": obs.last_step.value}),
            flight=flight,
            profiler=ProfileCapture(run_dir / "profile", tracer=tracer),
            tracer=tracer)
        # The fault-tolerance plane (ISSUE 4): when the gang coordinator
        # assigned a heartbeat dir, a daemon thread beats liveness every
        # interval and the loop keeps the step fresh (update_step) so
        # the monitor can tell DEAD from STRAGGLER.
        ft_dir = os.environ.get("TPUCFN_FT_DIR", "").strip()
        if ft_dir:
            from tpucfn.ft import HeartbeatWriter

            try:
                hb_s = float(os.environ.get("TPUCFN_FT_HEARTBEAT_S", "") or 1.0)
            except ValueError:
                hb_s = 1.0
            hb = HeartbeatWriter(ft_dir, host_id=host, interval_s=hb_s,
                                 role="trainer").start()
        t_start = time.perf_counter()
        return _train_loop_body(
            trainer, ds, mesh, args, items_per_step, extra_axes, run_eval,
            logger, timer, obs, t_start, run_dir, hb)
    finally:
        if hb is not None:
            hb.stop()
        if logger is not None:
            logger.close()
        if tracer is not None:
            tracer.close()
        if ledger is not None:
            ledger.close()
        if obs_srv is not None:
            obs_srv.close()


def _train_loop_body(trainer, ds, mesh, args, items_per_step, extra_axes,
                     run_eval, logger, timer, obs, t_start, run_dir,
                     hb=None):
    import jax

    from tpucfn.ckpt import CheckpointManager
    from tpucfn.data import prefetch_to_mesh
    from tpucfn.obs import profile_steps

    from tpucfn.ft import RESTORE_FAILED_RC, drain_requested
    from tpucfn.train.trainer import RestoreFailure

    ft_dir = os.environ.get("TPUCFN_FT_DIR", "").strip()
    with CheckpointManager(run_dir / "ckpt",
                           save_interval_steps=args.ckpt_every) as ckpt:
        # Restart implies resume: a relaunched job (restart supervisor,
        # operator re-run) picks up at its latest checkpoint without the
        # caller remembering --resume; --fresh opts out (SURVEY.md §5
        # failure row — recovery must not silently retrain from step 0).
        try:
            state, resumed = trainer.init_or_resume(
                jax.random.key(args.seed), ckpt, fresh=args.fresh)
        except RestoreFailure as e:
            # Distinguishable rc (ISSUE 7): the coordinator catches it,
            # blacklists the bad step, and retries from the previous
            # finalized one instead of crash-looping into give_up.
            print(f"checkpoint restore failed: {e}", flush=True)
            raise SystemExit(RESTORE_FAILED_RC)
        if resumed is not None:
            print(f"resumed from step {int(state.step)}", flush=True)

        total = args.steps or len(ds) * args.num_epochs
        halt = min(total, args.stop_after) if args.stop_after else total
        metrics = {}
        step = int(state.step)
        with profile_steps(run_dir / "profile", enabled=args.profile):
            # Disaggregated input plane (ISSUE 11): when the launcher
            # fanned out input hosts (TPUCFN_INPUT_ADDRS), the local
            # loader is swapped for the service client — resilient
            # stream (failover, degrade-to-local at the exact cursor)
            # behind a data_wait-driven adaptive prefetcher.  Without
            # the env this is ds.batches(None), byte-for-byte as before.
            from tpucfn.data.service import service_or_local_batches

            stream = service_or_local_batches(
                ds, num_epochs=None,
                on_degrade=lambda reason: print(
                    f"input plane degraded to local loading: {reason}",
                    flush=True))
            batches = iter(prefetch_to_mesh(stream, mesh,
                                            extra_axes=extra_axes,
                                            tracer=obs.tracer,
                                            first_step=step + 1))
            # Cross-host causality (ISSUE 20): the resilient stream
            # queues one wire context per batch it yields; popping
            # exactly one per batch CONSUMED here keeps the FIFO
            # pairing exact through any prefetch depth.  Local loaders
            # have no pop_link — every wait is then a local wait.
            pop_link = getattr(stream, "pop_link", None)
            _end = object()
            while True:
                # data_wait vs step vs ckpt: the three spans that say WHY
                # a slow step was slow (input pipeline vs compute vs
                # save) — per host, trace_id = the global step.  The wait
                # is recorded only once the loop commits to a step, so
                # the end-of-data drain never shows up as a phantom
                # step's data wait.
                t0_wait = time.monotonic()
                batch = next(batches, _end)
                t_wait = time.monotonic() - t0_wait
                if batch is _end or step >= halt:
                    break
                obs.record_data_wait(
                    step + 1, t0_wait, t_wait,
                    link=pop_link() if pop_link is not None else None)
                with obs.step(step + 1) as mark:
                    state, metrics = trainer.step(state, batch)
                    mark.dispatched()  # launched; from here the host waits
                    step = int(state.step)  # blocks -> honest step timing
                # what the step counted beside its loss (the loss function's
                # ``counters``, a sparse model's routing): ready on the device
                # since the wait above, so one small transfer, then one trace
                # line and a gauge each, and the log's line like the rest
                if "counters" in metrics:
                    counters = {k: float(v) for k, v in
                                jax.device_get(metrics.pop("counters")).items()}
                    obs.record_step_counters(step, counters)
                    metrics.update(counters)
                if hb is not None:
                    hb.update_step(step)  # step-lag signal for the monitor
                timer.tick()
                if t_start is not None:
                    # data staging + init/restore + first compile+step
                    logger.log(step, {"time_to_first_step": round(
                        time.perf_counter() - t_start, 2)})
                    t_start = None
                if step % args.log_every == 0 or step == halt:
                    logger.log(step, {**{k: float(v) for k, v in metrics.items()},
                                      "step_time": timer._last or 0.0,
                                      "data_wait_time": t_wait})
                if args.eval_every and step % args.eval_every == 0:
                    run_eval(state, step)
                # CheckpointManager gates on save_interval_steps; record
                # the span only when a save actually ran, else the ckpt
                # metric measures no-op call overhead.
                t0_ckpt = time.monotonic()
                if ckpt.save(step, state):
                    obs.record_ckpt(step, t0_ckpt,
                                    time.monotonic() - t0_ckpt)
                # Preemption drain (ISSUE 7): the coordinator asked the
                # gang to stop cleanly at a step boundary; the final
                # force-save below is the drain's zero-lost-work save.
                if ft_dir and drain_requested(ft_dir, step):
                    print(f"preemption drain: stopping cleanly at step "
                          f"{step}", flush=True)
                    break
            # A step-target/drain exit leaves the (unbounded) service
            # stream live: close it, or the prefetcher keeps buffering
            # up to its byte bound and the input host keeps decoding
            # batches nobody will consume through eval/final-save.
            close_stream = getattr(stream, "close", None)
            if close_stream is not None:
                try:
                    close_stream()
                except ValueError:
                    # A plain LOCAL generator can still be mid-__next__
                    # in the prefetch thread ("generator already
                    # executing") — close is best-effort cleanup there;
                    # the service-backed stream (what the close exists
                    # for) closes through its own object, not the
                    # generator protocol.
                    pass
            # ... and the prefetcher itself: its thread sits in a put with
            # the batches it placed ahead still on the devices.
            batches.close()
        run_eval(state, int(state.step))
        t0_ckpt = time.monotonic()
        if ckpt.save(int(state.step), state, force=True):
            obs.record_ckpt(int(state.step), t0_ckpt,
                            time.monotonic() - t0_ckpt)

    if jax.process_index() == 0:
        ips = timer.throughput(items_per_step)
        loss = float(metrics.get("loss", float("nan")))
        line = f"final: step={int(state.step)} loss={loss:.4f}"
        if ips:  # needs steady-state steps beyond the compile warmup
            line += (f" items/sec={ips:.1f}"
                     f" items/sec/chip={ips / jax.device_count():.1f}")
        print(line, flush=True)
    return state
