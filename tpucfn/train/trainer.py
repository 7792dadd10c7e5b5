"""The SPMD trainer: one jit-compiled program per step.

This collapses the reference's entire per-step pipeline — forward/backward
in the MXNet/TF C++ engine, gradients handed to ps-lite push/pull or
Horovod's fusion queue + NCCL ring (SURVEY.md §3.2-§3.4) — into a single
XLA program. The batch arrives sharded over the (data, fsdp) mesh axes,
params/optimizer state live wherever the sharding rules put them, and XLA
inserts every collective (grad all-reduce, FSDP all-gather/reduce-scatter,
TP psum) as part of the same fused computation. There is no framework-owned
wire protocol: the compiler owns the data path (SURVEY.md §5 last row).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpucfn.parallel.sharding import (
    ShardingRules,
    batch_spec,
    make_partition_spec,
    named_sharding_tree,
)
from tpucfn.train.state import TrainState

# loss_fn(params, model_state, batch, rng)
#   -> (loss, (metrics_dict, new_model_state))
# ``model_state`` carries mutable collections (batch_stats); return it
# unchanged (or {}) for stateless models.
LossFn = Callable[[Any, Any, Any, jax.Array], tuple[jax.Array, tuple[dict, Any]]]

# init_fn(rng) -> (params, model_state)
InitFn = Callable[[jax.Array], tuple[Any, Any]]


class RestoreFailure(RuntimeError):
    """A checkpoint EXISTS but restoring it failed (corruption,
    truncation, a half-written save that slipped past finalization).

    Distinct from "no checkpoint" (which quietly falls back to a fresh
    init) because the two demand opposite recoveries: a missing
    checkpoint means start over, a corrupt one means *retry from the
    previous step* — the trainer's caller should exit with
    ``tpucfn.ft.RESTORE_FAILED_RC`` so the gang coordinator can
    blacklist the bad step instead of crash-looping into give_up
    (ISSUE 7).

    Deliberately broad: any failure restoring an existing checkpoint
    maps here, including non-corruption causes (a sharding/config
    mismatch, a transient allocator failure).  The coordinator's
    response is bounded (``max_ckpt_retries``) and reversible — a
    "quarantined" step is a plain rename into ``<ckpt>/corrupt/`` the
    operator can move back — and with no earlier step to resume from
    it declines to retry and fails loudly rather than re-init fresh."""

    def __init__(self, step: int, cause: BaseException):
        super().__init__(
            f"restoring checkpoint step {step} failed: {cause!r}")
        self.step = step


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    donate_state: bool = True
    # Extra sharded batch dims after the leading batch axis, e.g.
    # ("context",) when sequence parallelism is on.
    batch_extra_axes: tuple[str | None, ...] = ()
    # Gradient accumulation: split each global batch into this many
    # sequential microbatches inside the step (lax.scan), average grads,
    # apply once. Raises the effective batch without raising peak
    # activation memory — the non-pipeline sibling of GPipe microbatching.
    grad_accum: int = 1
    # Exponential moving average of params (the diffusion-finetune
    # standard): tracked under model_state["ema"] post-update, so it
    # shards like the params, checkpoints with the state, and is ready
    # for eval/export. 0.0 disables.
    ema_decay: float = 0.0


class Trainer:
    """Binds (mesh, sharding rules, loss, optimizer) into jitted init/step.

    Usage::

        trainer = Trainer(mesh, rules, loss_fn, optax.adamw(1e-3), init_fn)
        state = trainer.init(jax.random.key(0))
        state, metrics = trainer.step(state, batch)   # batch: host-local
    """

    def __init__(
        self,
        mesh: Mesh,
        rules: ShardingRules,
        loss_fn: LossFn,
        tx: optax.GradientTransformation,
        init_fn: InitFn,
        config: TrainerConfig = TrainerConfig(),
        eval_loss_fn: LossFn | None = None,
    ):
        """``eval_loss_fn`` runs inference-mode semantics (BN running stats,
        no dropout); models with train/eval divergence must supply it or
        eval metrics are computed in train mode."""
        self.mesh = mesh
        self.rules = rules
        self.loss_fn = loss_fn
        self.eval_loss_fn = eval_loss_fn if eval_loss_fn is not None else loss_fn
        self.tx = tx
        self.init_fn = init_fn
        self.config = config
        self._jit_step = None
        self._jit_eval = None
        # Who hears of every program ``step`` and ``eval_step`` compile
        # (``TrainerObs.record_program``, set by the loop); None: nobody.
        self.on_program: Callable | None = None
        self._state_shardings = None
        self._abstract_state = None

    # ---- init ----------------------------------------------------------

    def _state_rules(self) -> ShardingRules:
        # Scalars and rng keys replicate; params/opt_state follow the param
        # rules (optax state mirrors the param tree structure under mu/nu/
        # etc., so path-regex rules written for params still match).
        return self.rules.extended([(r"(^|/)(step|rng|count)($|/)", P())])

    @staticmethod
    def _opt_rank_mismatch(path: str, spec, ndim: int):
        # Factored optimizer state (Adafactor v_row/v_col) mirrors the
        # param path at rank n-1, so the param rule's spec is over-long.
        # Replicate it: the factored vectors are ~params/dim in size, so
        # replication costs nothing next to resharding-rule surgery.
        if path.startswith("opt_state"):
            return P()
        raise ValueError(
            f"rule spec {spec} has {len(spec)} entries but {path!r} has "
            f"rank {ndim}")

    def _create_state(self, rng: jax.Array) -> TrainState:
        params_rng, step_rng = jax.random.split(rng)
        params, model_state = self.init_fn(params_rng)
        if self.config.ema_decay:
            if "ema" in (model_state or {}):
                raise ValueError(
                    "model_state already has an 'ema' entry; ema_decay "
                    "owns that key")
            model_state = {**(model_state or {}),
                           "ema": jax.tree.map(jnp.asarray, params)}
        return TrainState.create(params, self.tx, step_rng, model_state)

    def _abstract(self) -> Any:
        if self._abstract_state is None:
            self._abstract_state = jax.eval_shape(self._create_state, jax.random.key(0))
        return self._abstract_state

    def state_shardings(self) -> Any:
        if self._state_shardings is None:
            self._state_shardings = named_sharding_tree(
                self.mesh, self._state_rules(), self._abstract(),
                self._opt_rank_mismatch,
            )
        return self._state_shardings

    def init(self, rng: jax.Array) -> TrainState:
        """Initialize the state directly into its target sharding — params
        are *born sharded* on their owner devices (no host staging, no
        broadcast; the analogue of the reference's rank-0-initializes-then-
        KVStore-pushes startup, minus the wire traffic)."""
        from tpucfn.compilecache.jit import maybe_warm

        return maybe_warm(
            jax.jit(self._create_state, out_shardings=self.state_shardings()),
            label="train_init")(rng)

    def init_or_resume(self, rng: jax.Array, ckpt=None, *,
                       fresh: bool = False) -> tuple[TrainState, int | None]:
        """Resume-from-latest on startup (ISSUE 4): restore the latest
        checkpoint through ``ckpt`` (a :class:`tpucfn.ckpt.
        CheckpointManager`) into this trainer's abstract state, or init
        fresh when there is none (or ``fresh`` forces it).  Returns
        ``(state, resumed_step)`` with ``resumed_step=None`` for a fresh
        init — the one call a gang-restarted job needs to continue from
        the last saved step instead of retraining from 0.

        A checkpoint that exists but will not restore raises
        :class:`RestoreFailure` (never silently re-inits: losing the
        whole run to a corrupt latest step is the coordinator's call,
        not this method's)."""
        if ckpt is not None and not fresh:
            latest = ckpt.latest_step()
            if latest is not None:
                try:
                    return ckpt.restore(self.abstract_state()), latest
                except Exception as e:  # noqa: BLE001 — see docstring
                    raise RestoreFailure(latest, e) from e
        return self.init(rng), None

    def abstract_state(self) -> Any:
        """ShapeDtypeStructs with shardings attached — what checkpoint
        restore needs to re-materialize the state on a (possibly different)
        mesh (SURVEY.md §5 checkpoint/resume row)."""
        sh = self.state_shardings()
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            self._abstract(), sh,
        )

    # ---- the step's program ---------------------------------------------

    def _program(self, jitted, label: str):
        """The step's one lower -> compile path: the jitted function is
        lowered at the first call's arguments and compiled explicitly (by
        the fleet's artifact cache where a client is configured), the
        executable is kept and called from then on, and ``on_program``
        hears what was compiled.  ``.lower`` is the jitted function's."""
        from tpucfn.compilecache.jit import WarmJit, get_default_client

        def heard(compiled, **about):
            if self.on_program is not None:
                self.on_program(compiled, **about)

        return WarmJit(jitted, get_default_client(), label=label,
                       on_program=heard)

    # ---- step ----------------------------------------------------------

    def _grads(self, state: TrainState, batch: Any, step_rng: jax.Array):
        accum = self.config.grad_accum
        grad_fn = jax.value_and_grad(self.loss_fn, has_aux=True)
        if accum <= 1:
            (loss, (aux, new_model_state)), grads = grad_fn(
                state.params, state.model_state, batch, step_rng
            )
            return loss, aux, new_model_state, grads

        micro = jax.tree.map(
            lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
        )

        def body(carry, mb):
            grads_acc, loss_acc, aux_acc, mstate, i = carry
            (loss, (aux, mstate)), grads = grad_fn(
                state.params, mstate, mb, jax.random.fold_in(step_rng, i)
            )
            grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            loss_acc = loss_acc + loss
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
            return (grads_acc, loss_acc, aux_acc, mstate, i + 1), None

        zero_grads = jax.tree.map(jnp.zeros_like, state.params)
        mb0 = jax.tree.map(lambda x: x[0], micro)
        _, (aux0, _) = jax.eval_shape(
            lambda: self.loss_fn(state.params, state.model_state, mb0, step_rng)
        )
        zero_aux = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), aux0)
        (grads, loss, aux, new_model_state, _), _ = jax.lax.scan(
            body,
            (zero_grads, jnp.zeros((), jnp.float32), zero_aux, state.model_state,
             jnp.zeros((), jnp.int32)),
            micro,
        )
        inv = 1.0 / accum
        return (loss * inv,
                jax.tree.map(lambda a: a * inv, aux),
                new_model_state,
                jax.tree.map(lambda g: g * inv, grads))

    def _step_fn(self, state: TrainState, batch: Any):
        step_rng = jax.random.fold_in(state.rng, state.step)
        loss, aux, new_model_state, grads = self._grads(state, batch, step_rng)
        # a name for the trace's map (obs.program): metadata, nothing that runs
        with jax.named_scope("optimizer"):
            updates, new_opt = self.tx.update(grads, state.opt_state,
                                              state.params)
            new_params = optax.apply_updates(state.params, updates)
        if self.config.ema_decay:
            # Post-update EMA; owns model_state["ema"] (re-attached even
            # when a loss_fn rebuilds its model_state from scratch).
            d = self.config.ema_decay
            new_model_state = {**new_model_state, "ema": jax.tree.map(
                lambda e, p: e * d + p.astype(e.dtype) * (1.0 - d),
                state.model_state["ema"], new_params)}
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt,
            rng=state.rng,
        )
        return new_state, {"loss": loss, **aux}

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, batch_spec(self.config.batch_extra_axes))

    def step(self, state: TrainState, batch: Any):
        if self._jit_step is None:
            shardings = self.state_shardings()
            metric_spec = NamedSharding(self.mesh, P())
            self._jit_step = self._program(jax.jit(
                self._step_fn,
                in_shardings=(shardings, self.batch_sharding()),
                out_shardings=(shardings, metric_spec),
                donate_argnums=(0,) if self.config.donate_state else (),
            ), "train_step")
        return self._jit_step(state, batch)

    # ---- eval ----------------------------------------------------------

    def eval_step(self, state: TrainState, batch: Any) -> dict[str, jax.Array]:
        if self._jit_eval is None:
            def _eval(state, batch):
                loss, (aux, _) = self.eval_loss_fn(
                    state.params, state.model_state, batch, state.rng
                )
                return {"loss": loss, **aux}
            self._jit_eval = self._program(jax.jit(
                _eval,
                in_shardings=(self.state_shardings(), self.batch_sharding()),
                out_shardings=NamedSharding(self.mesh, P()),
            ), "train_eval")
        return self._jit_eval(state, batch)

    def param_spec(self) -> Any:
        return make_partition_spec(self._state_rules(), self._abstract(),
                                   self._opt_rank_mismatch)


class StepMark:
    """What :meth:`TrainerObs.step` yields: the loop calls
    ``dispatched()`` once ``trainer.step`` has returned, and that one
    clock reading divides the ``step`` span into ``step_dispatch`` and
    ``step_wait``."""

    def __init__(self, clock):
        self._clock = clock
        self.at: float | None = None

    def dispatched(self) -> None:
        self.at = self._clock()


class TrainerObs:
    """Observability for the canonical train loop phases.

    The loop a host actually lives in is ``data_wait → step → ckpt``
    repeated; this binds each phase to both planes at once — registry
    metrics (scrapeable via the per-host ``/metrics`` endpoint) and
    trace spans (one JSONL line per phase occurrence, host id attached,
    ``trace_id`` = the global step so ``tpucfn obs`` can line hosts up
    per step and name the straggler).  Phase timings are host-observed
    wall times: ``step`` includes the device dispatch AND the block on
    the result, which is the honest per-step number on an async runtime
    (same rule as StepTimer); the trace divides it into ``step_dispatch``
    and ``step_wait`` where the loop marks the hand-over.

    Usage (what examples/common.py's run_train_loop does)::

        obs = TrainerObs(registry, tracer)
        with obs.data_wait():   batch = next(it)
        with obs.step(step_no) as s:
            state, m = trainer.step(state, batch); s.dispatched(); ...
        with obs.ckpt(step_no): ckpt.save(step_no, state)
    """

    def __init__(self, registry=None, tracer=None, *, prefix: str = "train",
                 ledger=None, clock=time.monotonic, flight=None):
        """``ledger`` is a :class:`tpucfn.obs.goodput.GoodputLedger` (or
        None): every phase the loop reports is also attributed to the
        per-host goodput JSONL so ``tpucfn obs goodput`` can decompose
        the run's wall clock (ISSUE 5).  ``clock`` is injectable so the
        gauges are pinned with a fake clock and no TPU.

        ``flight`` is a :class:`tpucfn.obs.flight.FlightRecorder` (or
        None): every phase also lands one sample in the in-memory ring,
        plus an ``hbm`` device-memory sample per step — the last-N-
        seconds record a postmortem reads (ISSUE 6)."""
        from tpucfn.obs.goodput import GoodputLedger
        from tpucfn.obs.registry import default_registry
        from tpucfn.obs.trace import Tracer

        r = self.registry = (registry if registry is not None
                             else default_registry())
        self.tracer = tracer if tracer is not None else Tracer(None)
        self.prefix = prefix
        self.ledger = ledger if ledger is not None else GoodputLedger(None)
        self.clock = clock
        self.flight = flight
        # the step span that is open, as (step, span id), and where the
        # program compiled inside the first one came from
        self._open: tuple[int | None, int] | None = None
        self._outcome: str | None = None
        self.step_time = r.histogram(
            f"{prefix}_step_seconds", "host-observed step wall time")
        self.data_wait_time = r.histogram(
            f"{prefix}_data_wait_seconds",
            "time the step loop blocked on the input pipeline")
        self.ckpt_time = r.summary(
            f"{prefix}_ckpt_seconds", "checkpoint save-call time")
        self.steps_total = r.counter(
            f"{prefix}_steps_total", "completed optimizer steps")
        self.last_step = r.gauge(
            f"{prefix}_last_step", "most recent global step")
        # The live efficiency plane (ISSUE 5), exported per step on the
        # existing /metrics endpoint.
        self.step_time_g = r.gauge(
            f"{prefix}_step_time_s", "last host-observed step wall time")
        self.goodput_ratio_g = r.gauge(
            f"{prefix}_goodput_ratio",
            "productive step seconds / wall seconds since loop start")
        self._t0 = clock()
        self._productive_s = 0.0
        self._steps_seen = 0

    @contextlib.contextmanager
    def _phase(self, name: str, metric, step: int | None):
        t0 = self.clock()
        try:
            yield
        finally:
            dt = self.clock() - t0
            metric.observe(dt)
            self.tracer.record(name, start=t0, dur_s=dt, trace_id=step)
            self.ledger.account(name, dt, step=step)
            if self.flight is not None:
                self.flight.record(name, step=step, dur_s=dt)

    def record_program(self, compiled, *, label: str, outcome: str,
                       **clock_readings: float) -> None:
        """What ``Trainer.on_program`` is set to: one ``step_program`` span
        (``obs.program.record_program``) about a program the trainer has
        just compiled, ``trace_id`` the global step it compiled at and its
        parent the ``step`` span it fell in.  ``outcome`` (``hit``,
        ``miss``, ``fetch``) also decides the first step's goodput
        bucket."""
        from tpucfn.obs.program import record_program

        step, parent = self._open if self._open else (
            int(self.last_step.value) or None, None)
        if self._open:
            self._outcome = outcome
        try:
            record_program(self.tracer, compiled, label=label,
                           outcome=outcome, trace_id=step, parent_id=parent,
                           **clock_readings)
        except Exception:  # noqa: BLE001 — the span must not stop the job
            logging.getLogger(__name__).exception(
                "no step_program span for %s", label)

    def _compile_bucket(self) -> str:
        """``compile`` vs ``compile_cached`` vs ``compile_fetched`` for the
        first step (ISSUE 6/13), from where its program came: a fleet-fetched
        executable gets its own bucket so the warm-start plane's effect is
        visible in the ledger, a hit in a cache (JAX's persistent one or the
        local artifact store) is ``compile_cached``, and a compile, or a
        first step that compiled nothing the trainer heard of, keeps the
        plain ``compile`` charge."""
        return {"fetch": "compile_fetched",
                "hit": "compile_cached"}.get(self._outcome, "compile")

    def _record_step(self, step: int | None, dur_s: float) -> None:
        """Shared post-step bookkeeping: the first step of a process is
        compile-dominated and lands in the ``compile`` bucket — or
        ``compile_cached`` when a cache served its program
        (the StepTimer warmup-exclusion rule applied to
        accounting); steady steps are ``step`` and feed the live
        efficiency gauges."""
        self._steps_seen += 1
        if self.flight is not None:
            self.flight.record("step", step=step, dur_s=dur_s)
            self.flight.sample_device()
        if self._steps_seen == 1:
            self.ledger.account(self._compile_bucket(), dur_s, step=step)
            return
        self.ledger.account("step", dur_s, step=step)
        self._productive_s += dur_s
        self.step_time_g.set(dur_s)
        elapsed = self.clock() - self._t0
        if elapsed > 0:
            self.goodput_ratio_g.set(self._productive_s / elapsed)

    def data_wait(self, step: int | None = None):
        return self._phase("data_wait", self.data_wait_time, step)

    def record_data_wait(self, step: int | None, start: float,
                         dur_s: float, link=None) -> None:
        """Post-hoc form of :meth:`data_wait` (``start`` in
        ``time.monotonic()`` seconds) for loops that must first decide
        whether the fetched batch starts a real step — the end-of-data
        drain wait must not be recorded as a phantom step's data wait.
        ``link`` is the batch's wire context from the input plane
        (``ResilientBatchStream.pop_link()``), recorded as the span's
        remote parent (ISSUE 20): on the merged timeline this wait
        points at the input-host ``input_serve`` span that produced the
        batch; None (local batch, tracing off upstream) records a plain
        local wait."""
        self.data_wait_time.observe(dur_s)
        self.tracer.record("data_wait", start=start, dur_s=dur_s,
                           trace_id=step, remote_parent=link)
        self.ledger.account("data_wait", dur_s, step=step)
        if self.flight is not None:
            self.flight.record("data_wait", step=step, dur_s=dur_s)

    def step(self, step: int | None = None):
        """Times one step; yields a :class:`StepMark` whose
        ``dispatched()`` the loop calls once ``trainer.step`` has
        returned, which divides the ``step`` span into its children
        ``step_dispatch`` (the host launching the program) and
        ``step_wait`` (the host waiting for the chip) at one clock
        reading.  A loop that never calls it writes ``step`` alone."""
        @contextlib.contextmanager
        def _span():
            mark = StepMark(self.clock)
            # drawn before the span is written: a program compiled inside
            # the step names it as its parent
            sid = self.tracer.next_span_id()
            self._open = (step, sid)
            t0 = self.clock()
            try:
                yield mark
            finally:
                t1 = self.clock()
                self._open = None
                self.step_time.observe(t1 - t0)
                self.tracer.record("step", start=t0, end=t1, trace_id=step,
                                   span_id=sid)
                if mark.at is not None:
                    # the children share the mark, so their durations sum
                    # to the parent's
                    self.tracer.record("step_dispatch", start=t0, end=mark.at,
                                       trace_id=step, parent_id=sid)
                    self.tracer.record("step_wait", start=mark.at, end=t1,
                                       trace_id=step, parent_id=sid)
                self._record_step(step, t1 - t0)
            self.steps_total.add()
            if step is not None:
                self.last_step.set(step)
        return _span()

    def record_step_counters(self, step: int | None,
                             counters: dict[str, float]) -> None:
        """What a step counted beside its loss (the ``counters`` of its
        metrics): one ``step_metrics`` trace line a step with each counter
        as an attribute, and a gauge ``{prefix}_{name}`` each.  The loop
        calls it after the step's wait, with the values on the host."""
        self.tracer.record("step_metrics", start=self.clock(), dur_s=0.0,
                           trace_id=step, **counters)
        for name, value in counters.items():     # get-or-create by name
            self.registry.gauge(
                f"{self.prefix}_{name}",
                f"the step's counter {name} (its loss function's)").set(value)

    def ckpt(self, step: int | None = None):
        return self._phase("ckpt", self.ckpt_time, step)

    def record_ckpt(self, step: int | None, start: float,
                    dur_s: float) -> None:
        """Post-hoc form of :meth:`ckpt` for interval-gated save calls:
        record only saves that actually happened, or the percentiles
        measure no-op call overhead and read ~0 while real saves take
        seconds."""
        self.ckpt_time.observe(dur_s)
        self.tracer.record("ckpt", start=start, dur_s=dur_s, trace_id=step)
        self.ledger.account("ckpt", dur_s, step=step)
        if self.flight is not None:
            self.flight.record("ckpt", step=step, dur_s=dur_s)
