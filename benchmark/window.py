"""What the benchmark puts inside the program's loop, and nothing more.

``Window`` wraps the dataset: ``batches()`` passes the program's batches
through and stamps ``time.monotonic()`` (the clock of the program's spans)
each time the loop's prefetcher pulls one.  In steady state one pull is one
step, so the stamps give the window's exact step boundaries.  The window
opens at the first pull after the warm-up pulls (warm-up steps plus the
prefetcher's lead) once no compile of the program's runs in the background,
and closes at the first pull at or after ``seconds``; the stream then ends by
``WindowClosed``, which leaves the loop before its final save (gigabytes of
state that no request needs written, in every run of every later check).

``StepTap`` sits on ``trainer.step`` for the first steps only: it reads, on
the device, what the comparison needs of the state those steps produce, then
removes itself, so the window drives the program's own bound method.
"""

from __future__ import annotations

import threading
import time

from benchmark import compare

class WindowClosed(Exception):
    """Ends the stream when the window has closed."""


class Window:
    def __init__(self, ds, *, seconds: float, warmup_pulls: int,
                 keep_batches: int, trace_dir=None, trace_seconds: float = 0.0,
                 profiler: dict | None = None,
                 background_threads: tuple[str, ...] = ()):
        self.ds = ds
        self.seconds = seconds
        self.warmup_pulls = warmup_pulls
        self.keep_batches = keep_batches
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.profiler = profiler or {}
        # threads of the program that compile in the background after the
        # first step, by the names the cell's file gives
        self.background_threads = tuple(background_threads)
        self.pulls: list[float] = []
        self.kept: list[dict] = []
        self.open_index: int | None = None
        self.close_index: int | None = None
        self.traced: tuple[float, float] | None = None
        self._trace_t0: float | None = None

    def __len__(self):
        return len(self.ds)

    def __getattr__(self, name):
        return getattr(self.ds, name)

    def batches(self, num_epochs=None):
        inner = iter(self.ds.batches(num_epochs))
        while True:
            self._pull(time.monotonic())
            batch = next(inner)
            if len(self.kept) < self.keep_batches:
                self.kept.append(batch)
            yield batch

    def _pull(self, now: float) -> None:
        self.pulls.append(now)
        i = len(self.pulls) - 1
        if self.open_index is None:
            if i >= self.warmup_pulls and not self._background_busy():
                self.open_index = i
                if self.trace_dir is not None:
                    import jax

                    # device events only.  With the host's tracer on, every
                    # other ResNet step stalled for up to 1.4 s and the device
                    # read 77% idle against 5% (PERF.md, PR 24); the HLO
                    # protos swell the file tenfold and nothing reads them
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 0
                    opts.enable_hlo_proto = False
                    for k, v in self.profiler.items():
                        setattr(opts, k, v)
                    jax.profiler.start_trace(str(self.trace_dir),
                                             profiler_options=opts)
                    self._trace_t0 = time.monotonic()
            return
        opened = self.pulls[self.open_index]
        if self._trace_t0 is not None and now - opened >= self.trace_seconds:
            self._stop_trace()
        if now - opened >= self.seconds:
            self.close_index = i
            raise WindowClosed

    def _background_busy(self) -> bool:
        return any(t.name in self.background_threads and t.is_alive()
                   for t in threading.enumerate())

    def _stop_trace(self) -> None:
        import jax

        t1 = time.monotonic()
        jax.profiler.stop_trace()
        self.traced = (self._trace_t0, t1)
        self._trace_t0 = None

    def abandon(self) -> None:
        """Stop a trace that the window never stopped (a loop that failed)."""
        if self._trace_t0 is not None:
            self._stop_trace()

    # ---- what the stamps give ---------------------------------------------
    @property
    def closed(self) -> bool:
        return self.close_index is not None

    def steps(self) -> int:
        return self.close_index - self.open_index

    def span_s(self) -> float:
        return self.pulls[self.close_index] - self.pulls[self.open_index]

    def intervals_s(self) -> list[float]:
        p = self.pulls[self.open_index:self.close_index + 1]
        return [b - a for a, b in zip(p, p[1:])]


class StepTap:
    def __init__(self, trainer, *, steps: int, spec: dict, key):
        self.trainer = trainer
        self.steps = steps
        self.spec = spec
        self.key = key
        self.loss: list = []
        self.grad_norm = None
        self.delta_norm = None
        trainer.step = self  # shadows the bound method until removed

    def __call__(self, state, batch):
        state, metrics = type(self.trainer).step(self.trainer, state, batch)
        self.loss.append(metrics["loss"])
        if len(self.loss) == 1:
            self.grad_norm = compare.first_grad_norms(state.opt_state,
                                                      state.params)
        if len(self.loss) == self.steps:
            self.delta_norm = compare.delta_norms(state.params, self.spec,
                                                  self.key)
            del self.trainer.step
        return state, metrics

    def readings(self) -> dict:
        if len(self.loss) < self.steps:
            raise RuntimeError(
                f"the loop made {len(self.loss)} of the {self.steps} steps "
                "the comparison follows")
        return {"loss": [float(x) for x in self.loss],
                "grad_norm": compare.fetch(self.grad_norm),
                "delta_norm": compare.fetch(self.delta_norm)}
